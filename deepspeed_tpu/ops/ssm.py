"""State-space (Mamba-2) sequence ops for the serving path: the chunked
scan a prefill call runs and the one-step update a decode step runs,
each with its short causal convolution.

The recurrence, per head (``x_t`` a head's ``P`` channels, ``B_t`` and
``C_t`` the ``N``-wide input and output maps shared by the heads of a
group, ``A < 0`` one scalar a head)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: [P, N]
    y_t = S_t C_t + D x_t

**Groups.** ``B`` / ``C`` come as ``[T, N]`` (one group: every head
reads the same maps; Granite 4.0-H) or as ``[T, G, N]`` (``G`` groups:
head ``h`` of ``H`` reads group ``h // (H / G)``; Nemotron-H has 8).
The one-group form is the program it was before groups existed: the
group axis appears in no shape of it.

**Prefill** (:func:`ssd_chunked_scan`) is the state-space-duality form
(Dao & Gu 2024, arXiv:2405.21060, section 6): the sequence is cut into
chunks of ``chunk`` tokens; inside a chunk the output is one masked
``[chunk, chunk]`` product a head (scores ``C_i . B_j`` times the decay
``exp(sum_{j<k<=i} dt_k A)``, on the MXU in the compute dtype with
float32 accumulation); the state is passed from chunk to chunk, and from
call to call, in float32. **Decode** (:func:`ssm_decode_step`) is the
recurrence itself, one step for every row.

Precision, fixed by the configuration (`models/granite_hybrid.py`):
``dt``, ``exp(dt A)``, every cumulative decay and the state are float32
whatever the activations are; products accumulate in float32.

Padding: a token whose ``dt`` is 0 decays nothing and adds nothing, so
the caller masks a ragged tail by zeroing its ``dt``; a dead decode row
keeps its state bit for bit (``live``).

The prefill scan is one Pallas kernel call a mixer
(`ops/pallas/ssd_prefill.py`, ``ds_ssd_prefill``) wherever the call's
shapes meet the chip's tiles, and the plain XLA body below, under a scope
of the same name, where they do not (`ssd_kernel_takes`: shapes decide,
no option does); the decode step is plain XLA under ``ds_ssm_decode``.
Both sit inside the mixer's ``ds_ssm_scan``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import ssd_prefill as _kernel

SSD_PREFILL_NAME = _kernel.SSD_PREFILL_NAME
SSM_DECODE_NAME = "ds_ssm_decode"

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_kernel_takes(T, H, P, N, G, chunk, dtype):
    """Whether `ssd_chunked_scan` runs a call of these shapes as the
    kernel (all of a model's mixers or none: they share their shapes)."""
    return _kernel.kernel_takes(T, H, P, N, G, chunk,
                                jnp.dtype(dtype).itemsize)


def ssd_chunked_scan(x, dt, A, B, C, state, chunk):
    """One sequence through the recurrence in chunks.

    ``x`` ``[T, H, P]`` (compute dtype), ``dt`` ``[T, H]`` float32
    (after softplus; 0 on padding), ``A`` ``[H]`` float32 (negative),
    ``B`` / ``C`` ``[T, N]`` (one group) or ``[T, G, N]``, ``state``
    ``[H, P, N]`` float32 (the state before the first token). ``T`` is
    a multiple of ``chunk``. Returns ``(y [T, H, P] float32 without the
    D term, state after the last token)``. The kernel where it takes
    the shapes, `ssd_chunked_scan_xla` where it does not.
    """
    T, H, P = x.shape
    G = 1 if B.ndim == 2 else B.shape[1]
    if ssd_kernel_takes(T, H, P, B.shape[-1], G, chunk, x.dtype):
        return _kernel.ssd_chunked_scan(x, dt, A, B, C, state, chunk)
    return ssd_chunked_scan_xla(x, dt, A, B, C, state, chunk)


def ssd_chunked_scan_xla(x, dt, A, B, C, state, chunk):
    """`ssd_chunked_scan` in plain XLA: the ``[chunk, chunk]`` scores and
    decays of every chunk and head as whole tensors. What runs where the
    kernel does not take a call, and the oracle of the kernel's tests."""
    T, H, P = x.shape
    N = B.shape[-1]
    Q = min(int(chunk), T)
    if T % Q:
        raise ValueError(f"sequence {T} is not a multiple of the scan "
                         f"chunk {Q}")
    c = T // Q
    dtype = x.dtype
    # the heads' axes and their letters: ``h``, or with groups ``gh``
    # (group, head within it), the maps then carrying the ``g``
    if B.ndim == 2:
        hs, g, h = (H,), "", "h"
    else:
        hs, g, h = (B.shape[1], H // B.shape[1]), "g", "gh"
    ms = B.shape[1:]
    with jax.named_scope(SSD_PREFILL_NAME):
        a = (dt * A).reshape(c, Q, *hs)
        acum = jnp.cumsum(a, axis=1)                    # [c, Q, *hs] <= 0
        xdt = (x.astype(_F32) * dt[..., None]).reshape(c, Q, *hs, P)
        Bc, Cc = B.reshape(c, Q, *ms), C.reshape(c, Q, *ms)

        # inside a chunk: y_i = sum_{j<=i} (C_i.B_j) decay(j->i) dt_j x_j
        G = jnp.einsum(f"ci{g}n,cj{g}n->c{g}ij", Cc, Bc,
                       preferred_element_type=_F32)
        acum_h = jnp.moveaxis(acum, 1, -1)              # [c, *hs, Q]
        seg = acum_h[..., :, None] - acum_h[..., None, :]
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        M = jnp.expand_dims(G, G.ndim - 2) * \
            jnp.exp(jnp.where(causal, seg, -jnp.inf))
        y = jnp.einsum(f"c{h}ij,cj{h}p->ci{h}p", M.astype(dtype),
                       xdt.astype(dtype), preferred_element_type=_F32)

        # what each chunk adds to the state at its end, and its decay
        to_end = jnp.exp(acum[:, -1:] - acum)           # [c, Q, *hs]
        add = jnp.einsum(f"cj{h}p,cj{g}n->c{h}pn",
                         (xdt * to_end[..., None]).astype(dtype), Bc,
                         preferred_element_type=_F32)
        whole = jnp.exp(acum[:, -1])                    # [c, *hs]

        # from chunk to chunk: c is small (a prefill call holds a few)
        state = state.reshape(*hs, P, N)
        before = []
        for i in range(c):
            before.append(state)
            state = whole[i][..., None, None] * state + add[i]
        before = jnp.stack(before)                      # [c, *hs, P, N]
        y = y + jnp.exp(acum)[..., None] * jnp.einsum(
            f"ci{g}n,c{h}pn->ci{h}p", Cc.astype(_F32), before,
            precision=_HIGHEST, preferred_element_type=_F32)
    return y.reshape(T, H, P), state.reshape(H, P, N)


def _per_head(m, H):
    """A step's maps ``[R, N]`` or ``[R, G, N]`` as every head of
    ``[R, H, P, N]`` reads them."""
    if m.ndim == 2:
        return m.astype(_F32)[:, None, None, :]
    return jnp.repeat(m.astype(_F32), H // m.shape[1], axis=1)[:, :, None]


def ssm_decode_step(x, dt, A, B, C, state, live):
    """One step of every row. ``x`` ``[R, H, P]``, ``dt`` ``[R, H]``
    float32, ``A`` ``[H]``, ``B`` / ``C`` ``[R, N]`` or ``[R, G, N]``,
    ``state`` ``[R, H, P, N]`` float32, ``live`` ``[R]`` bool. Returns ``(y
    [R, H, P] float32 without the D term, new state)``; a row that is
    not live keeps its state."""
    with jax.named_scope(SSM_DECODE_NAME):
        decay = jnp.exp(dt * A)[:, :, None, None]
        xdt = x.astype(_F32) * dt[..., None]
        new = decay * state + xdt[..., None] * _per_head(B, x.shape[1])
        y = jnp.sum(new * _per_head(C, x.shape[1]), axis=-1)
        state = jnp.where(live[:, None, None, None], new, state)
    return y, state


def causal_conv_prefill(seq, window, weight, bias, n_valid):
    """Depthwise causal convolution of one sequence that continues a
    window. ``seq`` ``[T, C]``, ``window`` ``[K-1, C]`` (the K-1 inputs
    before ``seq[0]``; zeros at the start of a prompt), ``weight``
    ``[K, C]`` (``weight[K-1]`` multiplies the current input), ``bias``
    ``[C]`` or None (a convolution without one: no add is traced).
    Returns ``(out [T, C] float32, the window after the ``n_valid`` true
    tokens)``: the tail past ``n_valid`` is padding and must not enter
    the next call's window.

    What goes through is the caller's: ``seq`` may be a gated input (the
    window then holds the gated values, `models/lfm2_moe.py`'s ``b *
    x``), and an activation, where the model has one, is applied to
    ``out`` by the caller. No activation and no gate is applied here."""
    K = weight.shape[0]
    T = seq.shape[0]
    full = jnp.concatenate([window.astype(seq.dtype), seq], axis=0)
    w = weight.astype(_F32)
    out = None if bias is None else bias.astype(_F32)[None, :]
    for k in range(K):
        tap = w[k][None, :] * full[k:k + T].astype(_F32)
        out = tap if out is None else out + tap
    # token t sits at full[t + K - 1]: the K-1 inputs up to the last
    # true token n_valid - 1 start at full[n_valid]
    return out, jax.lax.dynamic_slice_in_dim(full, n_valid, K - 1, 0)


def causal_conv_step(new, window, weight, bias, live):
    """One step of every row. ``new`` ``[R, C]``, ``window``
    ``[K-1, R, C]`` (oldest first), ``bias`` ``[C]`` or None. Returns
    ``(out [R, C] float32, the window moved on by one for live rows)``;
    a row that is not live keeps its window to the bit. As
    `causal_conv_prefill`: a gated ``new`` is the caller's, and so is
    any activation of ``out``."""
    K = weight.shape[0]
    w = weight.astype(_F32)
    if bias is None:
        out = w[K - 1][None, :] * new.astype(_F32)
    else:
        out = bias.astype(_F32)[None, :] + \
            w[K - 1][None, :] * new.astype(_F32)
    for k in range(K - 1):
        out = out + w[k][None, :] * window[k].astype(_F32)
    moved = jnp.concatenate([window[1:], new.astype(window.dtype)[None]],
                            axis=0)
    return out, jnp.where(live[None, :, None], moved, window)
