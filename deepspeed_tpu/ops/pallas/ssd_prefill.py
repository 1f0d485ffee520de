"""The chunked Mamba-2 scan of one prefill call as one kernel.

`ops/ssm.py` has the recurrence, the state-space-duality form a prefill
call runs and its precision; this file is that form with a scan chunk's
``[Q, Q]`` matrices in VMEM. Plain XLA writes the scores ``C B^T``, the
decay ``exp(acum_i - acum_j)`` and their product out to HBM as float32
``[chunks, heads, Q, Q]`` tensors and reads them back (16 MB a mixer a
call at 64 heads and two chunks of 256; 8 x 128 heads of ``[128, 128]``
with groups); here they are built, used and dropped in a grid step.

- **the grid is ``(head blocks, chunks)``**, the chunk axis last and
  walked in order. A grid step takes one scan chunk of ``hb`` heads
  that read one B/C group (sixteen at both serving cells' shapes): their
  tokens of ``x`` (one lane block of the mixer's own ``[T, H * P]``),
  the group's ``B`` and ``C`` (one lane block of ``[T, G * N]``: the
  group is the head block's index, no map is repeated through HBM), and
  the heads' ``dt`` and running ``dt A`` twice, tokens down the sublanes
  (a head's column meets the chunk's rows) and tokens along the lanes
  (a head's row meets its columns): two small arrays made outside, so
  that no ``[Q]`` vector is turned in the kernel.
- **the state is in VMEM from the call's first chunk to its last, and
  turned**: a scratch ``[N, hb * P]`` float32, the state's ``N`` entries
  down the sublanes and the heads' channels along the lanes, filled
  (and turned) from the state that came in at chunk 0 and turned back
  into the state output's block after the last chunk. Turned, what the
  state gives the tokens is ``C S^T`` and what the chunk adds is ``B^T
  (x dt)``, both plain products whose result has the heads' channels
  along the lanes like ``x`` and ``y``, and a head's decay over the
  chunk scales lanes; as stored (``[hb * P, N]``) the addition needs
  ``x dt`` turned every chunk (``[Q, hb * P]``, eight times ``B``) and
  the decay a value a sublane block, which Mosaic cannot broadcast from
  a ``[1, 1]`` corner.
- **heads go two to a tile** (`_LANES` / ``P``): a head's 64 channels
  fill half a vreg and half the MXU's width, so ``x dt``, the carried
  read, the state and ``y`` are handled a 128-lane tile of two heads at
  a time, each lane carrying its own head's ``dt`` and decays (a select
  by the lane's head); each head's ``M`` multiplies the whole tile (the
  product is as wide as the MXU either way) and a lane keeps its own
  head's result.
- a tile's grid step: ``C S^T`` (the state before the chunk); then a
  block of `_ROWS` rows at a time, against the columns up to the
  block's last (the causal mask leaves the rest zero: a quarter of a
  chunk of 256 is never built), a head's decay ``exp(acum_i - acum_j)``
  under the mask, ``M = scores x decay`` in the compute dtype, ``y = M
  (x dt) + exp(acum) (C S^T)``; then the state moves on, ``exp(acum_Q)
  S^T + B^T (x dt exp(acum_Q - acum))``. The group's scores ``C B^T``
  are computed once a grid step.
- **precision is `ops/ssm.py`'s**: ``M``, ``x dt`` and the state's
  increments meet the MXU in the compute dtype with float32
  accumulators; ``dt``, every decay and the state are float32; the
  state meets ``C`` as three bfloat16 terms whose sum is the float32
  state to its last bit where ``C`` is bfloat16 (what the highest
  precision computes when one operand has no lower terms: the other
  three of its six passes multiply zeros), and at the highest precision
  itself where it is not.

The call is jitted, so a model's mixers share one trace and one
lowering. Off-TPU it runs in Pallas interpret mode;
`tests/unit/test_tpu_compile.py` compiles it for a described v5e at the
two serving cells' shapes.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the HLO and in a device trace
SSD_PREFILL_NAME = "ds_ssd_prefill"

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

# heads a grid step at most (each is unrolled in the body), the lanes of
# a tile of heads, the rows of a chunk built at a time, and what a step's
# blocks and temporaries may take of VMEM
_HEADS_A_STEP = 16
_LANES = 128
_ROWS = 128
_VMEM_BUDGET = 10 << 20


def _step_bytes(hb, Q, P, N, itemsize):
    """VMEM of one grid step: the pipelined blocks twice (``x``, ``y``,
    the state in and out), the turned state, and a head's ``[Q, Q]``
    temporaries."""
    tokens, state = Q * hb * P, hb * P * N * 4
    return 2 * (tokens * (itemsize + 4) + 2 * state) + state + 6 * Q * Q * 4


def head_block(H, G, P, N, Q, itemsize):
    """Heads a grid step: the most, up to `_HEADS_A_STEP`, that divide a
    group's heads and fit VMEM."""
    per_group = H // G
    for hb in range(min(per_group, _HEADS_A_STEP), 0, -1):
        if per_group % hb == 0 and \
                _step_bytes(hb, Q, P, N, itemsize) <= _VMEM_BUDGET:
            return hb
    return 0


def _heads_a_tile(hb, P):
    """Heads whose ``P`` channels lie side by side on one tile of
    `_LANES` lanes (two of 64), so that what the kernel does a channel
    fills the tile; 1 where they do not divide."""
    pack = max(1, _LANES // P)
    return pack if _LANES % P == 0 and hb % pack == 0 else 1


def _interpret():
    return jax.devices()[0].platform != "tpu"


def kernel_takes(T, H, P, N, G, chunk, itemsize):
    """Whether the kernel takes a call, from its shapes. Compiled, the
    blocks must meet the chip's tiles: a scan chunk and a state width of
    whole 128-lane tiles and a head block whose ``hb * P`` channels are;
    interpreted (off-TPU), whole sublanes of tokens are enough. The
    engine's counter asks here too."""
    Q = min(int(chunk), T)
    if T % Q or H % G or Q % 8:
        return False
    hb = head_block(H, G, P, N, Q, itemsize)
    if not hb:
        return False
    return _interpret() or \
        (Q % 128 == 0 and N % 128 == 0 and (hb * P) % 128 == 0)


def _split3(s):
    """A float32 array as three bfloat16 terms that sum to it."""
    terms = []
    for _ in range(3):
        t = s.astype(jnp.bfloat16)
        terms.append(t)
        s = s - t.astype(_F32)
    return terms


def _nt(a, b, **kw):
    """``a b^T``, float32 out."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32, **kw)


def _scan_kernel(Q, P, hb, pack, n_chunks, dtype):
    W = pack * P                                        # a tile's lanes
    # rows of the chunk at a time: a block of rows meets the columns up
    # to its own last (the causal mask leaves the rest zero)
    RB = _ROWS if Q % _ROWS == 0 else Q

    def kernel(x_ref, cols_ref, rows_ref, b_ref, c_ref, s_in, y_ref, s_ref,
               st_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            st_ref[...] = s_in[...].T                   # [N, hb P]

        Bc, Cc = b_ref[...], c_ref[...]                 # [Q, N]
        scores = _nt(Cc, Bc)                            # [Q, Q]
        Bt = Bc.T                                       # [N, Q]
        cols, rows = cols_ref[0], rows_ref[0]           # [Q, 2 hb], [hb, Q]
        dt, acum = cols[:, :hb], cols[:, hb:]
        grown = jnp.exp(acum)                           # since the chunk began
        to_end = jnp.exp(acum[Q - 1:Q] - acum)
        def lane_head(rows):
            """Which of a tile's heads a lane belongs to, ``[rows, W]``
            (built at each height it is used at: a slice of an iota
            aborts Mosaic's layout pass)."""
            return jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1) // P

        def spread(per_head, first):
            """The tile's heads' columns of ``per_head`` ``[rows, hb]``,
            each over its head's ``P`` lanes: ``[rows, W]``."""
            out = per_head[:, first:first + 1]
            for j in range(1, pack):
                out = jnp.where(lane_head(per_head.shape[0]) == j,
                                per_head[:, first + j:first + j + 1], out)
            return out

        # a block of rows against the columns before its end: the mask
        causal = {
            lo: lo + jax.lax.broadcasted_iota(jnp.int32, (RB, lo + RB), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (RB, lo + RB), 1)
            for lo in range(0, Q, RB)}

        def decayed_scores(h, lo, hi):
            """``scores x decay`` of head ``h``, rows ``lo:hi`` against
            the columns before ``hi``, in the compute dtype."""
            seg = acum[lo:hi, h:h + 1] - rows[h:h + 1, :hi]
            return (scores[lo:hi, :hi] * jnp.exp(
                jnp.where(causal[lo], seg, -jnp.inf))).astype(dtype)

        # a tile of `pack` heads, W lanes, at a time
        for first in range(0, hb, pack):
            at = slice(first * P, first * P + W)
            St = st_ref[:, at]                          # [N, W]
            xdt = x_ref[:, at].astype(_F32) * spread(dt, first)
            xb = xdt.astype(dtype)
            # what the state before the chunk gives every token
            if Cc.dtype == jnp.bfloat16:
                read = sum(jnp.dot(Cc, term, preferred_element_type=_F32)
                           for term in _split3(St))
            else:
                read = jnp.dot(Cc.astype(_F32), St, precision=_HIGHEST,
                               preferred_element_type=_F32)
            carried = spread(grown, first) * read
            for lo in range(0, Q, RB):
                hi = lo + RB
                # every head of the tile against all the tile's lanes (the
                # product is as wide as the MXU either way); a lane keeps
                # its own head's
                y = None
                for j in range(pack):
                    own = jnp.dot(decayed_scores(first + j, lo, hi),
                                  xb[:hi], preferred_element_type=_F32)
                    y = own if y is None else \
                        jnp.where(lane_head(RB) == j, own, y)
                y_ref[lo:hi, at] = y + carried[lo:hi]
            # the state moves on: its decay over the chunk, and the
            # chunk's own addition B^T (x dt to_end)
            xe = (xdt * spread(to_end, first)).astype(dtype)
            st_ref[:, at] = spread(grown[Q - 1:Q], first) * St + \
                jnp.dot(Bt, xe, preferred_element_type=_F32)

        @pl.when(i == n_chunks - 1)
        def _():
            s_ref[...] = st_ref[...].T

    return kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_call(x, dt, A, B, C, state, *, chunk, interpret):
    T, H, P = x.shape
    N = B.shape[-1]
    G = 1 if B.ndim == 2 else B.shape[1]
    Q = min(chunk, T)
    c = T // Q
    dtype = x.dtype
    hb = head_block(H, G, P, N, Q, dtype.itemsize)
    blocks = H // hb
    pack = _heads_a_tile(hb, P)
    # the running sum of dt A inside each chunk, as the tokens lie; with
    # dt, a head block's columns [blocks, T, 2 hb] and its rows
    # [blocks, hb, T]
    acum = jnp.cumsum((dt * A).reshape(c, Q, H), axis=1).reshape(T, H)
    cols = jnp.concatenate([dt.reshape(T, blocks, hb),
                            acum.reshape(T, blocks, hb)],
                           axis=-1).swapaxes(0, 1)
    rows = acum.T.reshape(blocks, hb, T)

    tokens = lambda h, i: (i, h)                        # noqa: E731
    maps = lambda h, i: (i, h * hb * G // H)            # noqa: E731
    heads = lambda h, i: (h, 0)                         # noqa: E731
    call = pl.pallas_call(
        _scan_kernel(Q, P, hb, pack, c, dtype),
        name=SSD_PREFILL_NAME,
        grid=(blocks, c),
        in_specs=[pl.BlockSpec((Q, hb * P), tokens),
                  pl.BlockSpec((1, Q, 2 * hb), lambda h, i: (h, i, 0)),
                  pl.BlockSpec((1, hb, Q), lambda h, i: (h, 0, i)),
                  pl.BlockSpec((Q, N), maps),
                  pl.BlockSpec((Q, N), maps),
                  pl.BlockSpec((hb * P, N), heads)],
        out_specs=[pl.BlockSpec((Q, hb * P), tokens),
                   pl.BlockSpec((hb * P, N), heads)],
        out_shape=[jax.ShapeDtypeStruct((T, H * P), _F32),
                   jax.ShapeDtypeStruct((H * P, N), _F32)],
        # the state, turned: N down the sublanes, the heads' channels
        # along the lanes
        scratch_shapes=[pltpu.VMEM((N, hb * P), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(SSD_PREFILL_NAME):
        y, state = call(x.reshape(T, H * P), cols, rows,
                        B.reshape(T, G * N), C.reshape(T, G * N),
                        state.reshape(H * P, N))
    return y.reshape(T, H, P), state.reshape(H, P, N)


def ssd_chunked_scan(x, dt, A, B, C, state, chunk):
    """`ops.ssm.ssd_chunked_scan` as one kernel call, for a call that
    `kernel_takes`. The compiled kernel on TPU, Pallas interpret mode
    elsewhere."""
    return _scan_call(x, dt.astype(_F32), A.astype(_F32), B, C,
                      state.astype(_F32), chunk=int(chunk),
                      interpret=_interpret())
