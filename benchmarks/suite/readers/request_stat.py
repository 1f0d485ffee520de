"""A statistic of ``to - from`` over the program's own per-request
stamps (the ``serve/request`` records of the program's ring, see
``program_ring.py``). ``field_from`` and ``field_to`` name stamps
(``submit_t``, ``admit_t``, ``first_token_t``, ``first_return_t``,
``finish_t``); ``field_from`` may be ``due``, the instant the cell's
generator had the request due. ``over``: ``due_in_window`` takes the
requests due in the measured window (the driver's own choice for time
to first token), ``ending_in_window`` those that finished in it.
``stat`` is ``median``, ``mean`` or ``p<q>``; ``scale`` 1000 turns
seconds into ms. ``None`` where the ring holds nothing of the window,
or no such request carries both stamps."""

from benchmarks.suite import program_ring


def read(ctx, result, field_from, field_to, stat, over, scale=1.0):
    v = program_ring.view(ctx, result)
    if v is None:
        return None
    reqs = program_ring.requests(ctx, v)
    due = {}
    if field_from == "due" or over == "due_in_window":
        due = program_ring.due_times(ctx, v)
    if over == "due_in_window":
        chosen = [r for r in reqs if v.w0 <= due.get(r, v.w1) < v.w1]
    elif over == "ending_in_window":
        chosen = [r for r in reqs if v.w0 <= reqs[r]["finish_t"] < v.w1]
    else:
        raise ValueError(f"unknown over {over!r}")
    values = []
    for rid in chosen:
        start = due.get(rid) if field_from == "due" \
            else reqs[rid][field_from]
        end = reqs[rid][field_to]
        if start is not None and end is not None:
            values.append(end - start)
    if not values:
        return None
    return scale * program_ring.statistic(values, stat)
