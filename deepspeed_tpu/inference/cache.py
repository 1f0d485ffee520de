"""Paged KV cache: one pool of fixed-size pages per layer.

One cache = one statically-shaped pool per layer, ``[n_pages, n_head,
head_dim, page_size]`` for keys and values (``scan_layers`` models
stack a leading layer axis so the whole cache rides the same
``lax.scan`` as the params): a page's positions lie on the TPU's 128
lanes and ``head_dim`` on the sublanes, so the array's default tiled
layout is the one the flash kernel's ``[n_head, head_dim, block_k]``
blocks (all heads of a row to a fetch) are cut from, with no lane
padding at ``head_dim`` 64 — XLA re-lays nothing out around the
kernel. The price is that one position is one lane of every tile of
its page, so a write reads and writes back the page's whole ``[n_head,
head_dim, page_size]`` slab (:func:`paged_write_kv`), indexing
dynamically on the page axis alone; a flash decode step's write costs
no read of its own, because the kernel has the row's last block in
VMEM already and writes it back with the new lane in it
(:func:`cached_attention`). The pool is addressed through
per-row page tables (``[B, pages_per_row]`` int32) that enter the
compiled programs as plain data. The pool shape and the table shape
are both static, so admission, eviction, page allocation, freeing,
prefix sharing and host-tier park/resume are pure host-side metadata
churn and never change a compiled shape, which is what keeps the
decode loop at exactly one compile (`engine.compile_counts`).
Physical page 0 is the TRASH page: the allocator never hands it out,
unallocated table entries point at it, and the dense decode step's
inactive rows write their garbage token there (the flash kernel writes
nothing for them), so every gather/scatter stays in-bounds without
per-row branches.

Causality comes from explicit positions, not shapes: every write lands
at the token's absolute position and every read masks cache index
``s`` unless ``s <= query position``. A slot past a row's live prefix
is either stale (from the page's previous tenant) or garbage from a
padded prefill chunk — both masked, and both overwritten before the
mask ever exposes them (the decode step writes position ``p`` before
attending to it).

Optional int8/fp8 storage reuses the wire-codec recipe from
``runtime/comm/codecs.py`` (absmax scale into the codec's ``qmax``,
zero guard, round+clip for int) at per-(page, position, head) scale
granularity — one f32 scale per head vector, the KV analog of the
per-chunk wire scales.

**Recurrent leaves beside the pool** (ISSUE 31). A layer that keeps a
state instead of keys and values (a state-space mixer) holds, under its
name in the same cache tree, leaves with one entry a batch row: fixed
size whatever the row's length, owned by the row's SLOT, overwritten
from zero by the prefill chunk that starts a prompt, carried from chunk
to chunk and from decode step to decode step, never paged. The spec
lists them (``recurrent_layers``, ``recurrent_leaves``); both kinds of
leaf are donated to and updated in place by the same two programs.
What moves or shares pages (the prefix cache, park/resume, speculative
verify, the disaggregated hand-off, a ``model`` mesh axis) knows no
state and refuses such a spec: :class:`RecurrentStateUnsupported`.

**A latent pool** (ISSUE 34). A layer of latent attention
(`models/mla_moe.py`) keeps, for a token, one compressed vector that
stands for every head's key and value, and one rotary key that all
heads share: ``latent_v_dim + rope`` numbers (512 + 64) where per-head
keys and values would be ``2 x heads x head_dim`` (32,768). Its pool is
**one leaf a layer**, ``k`` ``[n_pages, 1, latent_v_dim + rope,
page_size]``: one "head", no ``v`` leaf, positions on the lanes like
every other pool. In the absorbed form the whole vector is the key of
every query head and its leading ``latent_v_dim`` entries are the
value, so the decode kernel reads a block once for both
(`flash_decode_paged`), and :func:`payload_shape`,
:func:`_layer_leaves`, the writes and :func:`paged_read_kv` are the
same code with one leaf less. A prefill chunk attends over the row's
live prefix in blocks (:func:`latent_prefill_attention`): the dense
path's ``[heads, chunk, bucket]`` scores are 4.4 GB at 64 heads, a
chunk of 1024 and a bucket of 16,896. Pages are pages: the allocator,
the prefix cache and park/resume move latent pages like any others.
**A latent pool beside recurrent leaves** (ISSUE 55;
`models/ling_hybrid.py`: one latent layer in eight, a delta-rule state
in the rest) is one spec with both: the two kinds of leaf lie under
different layers' names and nothing in the tree or the two programs
needed to change. What refuses either kind refuses it, and a feature
that refuses both says both reasons at once
(:class:`LatentAndRecurrentUnsupported`, caught by either name).
What knows per-head vectors refuses a latent pool
(:class:`LatentPoolUnsupported`): the int8 / fp8 codecs (one scale a
576-wide vector is another precision than one a head of 64; not
measured), a ``model`` mesh axis (one head cannot be split), and
speculative verify and the tiers, which nothing has run on one.

**Groups of page layers** (ISSUE 47). A model whose page layers are not
all alike (`models/mimo_v2.py`: full layers with 4 key heads beside
window layers with 8, keys of 192 over values of 128 in both) lists
them as :class:`PageGroup` s: each with its layers' names, its key
heads, its key and value widths and its window (0 = full). A layer's
``k`` leaf is ``[n_pages, heads, head_dim, page]`` and its ``v`` leaf
``[n_pages, heads, v_dim, page]``. A full group's pages are the pool
above. **A window group's rows keep a ring**: ``window // page_size +
1`` pages a row, from a pool of ``max_batch`` rings (and its own trash
page 0); position ``p`` lies in ring entry ``(p // page_size) % ring``
at lane ``p % page_size``, so a row holds what its window can reach
and the page being written, whatever its length. A row's page table
is one array: the full groups' ``pages_per_row`` entries, then the
ring's (``table_width``; :func:`split_table`). What a ring entry holds
is known by position, never by where it lies: entry ``j`` of a row at
``p`` holds the largest position ``<= p`` that maps to it, and the mask
admits it iff that position is inside the window. The models served
before are one group with equal widths and no window, and their specs,
trees and programs are what they were. What moves, shares or rolls back
pages refuses a ring (:class:`WindowRingUnsupported`).
"""

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime.comm.codecs import CODECS, get_codec


class RecurrentStateUnsupported(ValueError):
    """A serving feature that moves, shares or rolls back pages was
    asked of a model whose cache holds per-slot recurrent leaves, which
    that feature cannot carry yet. Raised when the engine (or the
    feature) is built, before anything is traced."""

    def __init__(self, feature, why):
        self.feature = feature
        super().__init__(
            f"{feature} cannot serve a model with a recurrent state "
            f"beside its KV pages: {why}")


class LatentPoolUnsupported(ValueError):
    """A serving feature that assumes per-head keys and values (a
    ``v`` leaf, a scale a head vector, a head axis to shard) was asked
    of a latent pool. Raised when the spec or the engine is built,
    before anything is traced."""

    def __init__(self, feature, why):
        self.feature = feature
        super().__init__(
            f"{feature} cannot serve a pool of latents (one leaf a "
            f"layer, one head): {why}")


class WindowRingUnsupported(ValueError):
    """A serving feature that moves, shares or rolls back pages was
    asked of a model with a window group, whose rows keep a ring of
    pages that are overwritten as the row grows. Raised when the engine
    (or the feature) is built, before anything is traced."""

    def __init__(self, feature, why):
        self.feature = feature
        super().__init__(
            f"{feature} cannot serve a model whose window layers keep a "
            f"ring of pages: {why}")


@dataclasses.dataclass(frozen=True)
class PageGroup:
    """Page layers that are alike: their names in the cache tree, their
    key heads, the widths of a key and of a value, their window (0: a
    full layer, whose row keeps every page) and their pool's pages
    (trash page included)."""
    name: str
    layers: tuple
    n_head: int
    head_dim: int
    v_dim: int
    window: int = 0
    n_pages: int = 0

    def bytes_per_token(self, itemsize):
        return len(self.layers) * self.n_head * \
            (self.head_dim + self.v_dim) * itemsize


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Static shape + storage format of one engine's cache: the page
    pools of the layers that keep keys and values and, for a hybrid
    model, the per-slot leaves of the layers that keep a state."""
    n_layer: int                    # layers that hold pages
    max_batch: int
    max_seq: int
    n_head: int                     # the pool's heads: key/value heads
    head_dim: int
    dtype: Any = jnp.bfloat16       # storage dtype (codec dtype when quantized)
    codec: Optional[str] = None     # None | "int8" | "f8e4m3fn" | "f8e5m2"
    stacked: bool = False           # scan_layers layout (leading layer axis)
    page_size: int = 0              # positions a page; divides max_seq
    n_pages: int = 0                # pool pages incl. the trash page
    # the page layers' names in the cache tree; () = h_<i> (stacked: h)
    layers: tuple = ()
    # layers that hold recurrent leaves, and those leaves, alike in
    # every such layer: (leaf, shape with its max_batch axis, dtype)
    recurrent_layers: tuple = ()
    recurrent_leaves: tuple = ()
    # > 0: a latent pool. One leaf a layer (``k``, no ``v``), whose
    # first ``latent_v_dim`` of ``head_dim`` entries are also the value
    latent_v_dim: int = 0
    # page layers that are not all alike, as PageGroups; () = one group
    # of ``layers`` with ``n_head`` heads of ``head_dim`` and no window
    groups: tuple = ()

    @property
    def pages_per_row(self):
        """Pages covering one row's max_seq span: a full group's part
        of a row's page table, and all of it where no group has a
        window."""
        return self.max_seq // self.page_size

    @property
    def page_groups(self):
        """The spec's groups; of a spec that lists none, its one."""
        if self.groups:
            return self.groups
        names = self.layers or (("h",) if self.stacked else tuple(
            f"h_{i}" for i in range(self.n_layer)))
        return (PageGroup("pages", tuple(names), self.n_head, self.head_dim,
                          self.head_dim, 0, self.n_pages),)

    @property
    def ring_pages(self):
        """Pages of a row's ring (0: no group has a window): what the
        window reaches and the page being written."""
        window = max((g.window for g in self.groups), default=0)
        return window // self.page_size + 1 if window else 0

    @property
    def table_width(self):
        """A row's page table: ``pages_per_row`` entries of the full
        groups' pool, then ``ring_pages`` of the window groups'."""
        return self.pages_per_row + self.ring_pages

    @property
    def state_bytes_per_slot(self):
        """Bytes of recurrent state one batch row owns, all layers."""
        per_layer = sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for _, shape, dtype in self.recurrent_leaves) // self.max_batch
        return per_layer * len(self.recurrent_layers)


class LatentAndRecurrentUnsupported(RecurrentStateUnsupported,
                                    LatentPoolUnsupported):
    """A serving feature that refuses a recurrent state and refuses a
    latent pool was asked of a spec that holds both
    (`models/ling_hybrid.py`): one error with both reasons, caught by
    either name."""

    def __init__(self, feature, why_recurrent, why_latent):
        self.feature = feature
        ValueError.__init__(
            self, f"{feature} cannot serve a model with a recurrent state "
            f"beside a pool of latents: {why_recurrent}; and {why_latent}")


def refuse_recurrent_or_latent(spec, feature, why_recurrent, why_latent):
    """`refuse_recurrent` and `refuse_latent` of one feature: a spec that
    is both is refused once, with both reasons."""
    if spec.recurrent_layers and spec.latent_v_dim:
        raise LatentAndRecurrentUnsupported(feature, why_recurrent,
                                            why_latent)
    refuse_recurrent(spec, feature, why_recurrent)
    refuse_latent(spec, feature, why_latent)


def refuse_recurrent(spec, feature, why):
    """The one check of everything that knows pages only."""
    if spec.recurrent_layers:
        raise RecurrentStateUnsupported(feature, why)


def refuse_window_ring(spec, feature, why):
    """The one check of everything that takes a row's pages for the
    whole of its past."""
    if spec.ring_pages:
        raise WindowRingUnsupported(feature, why)


def refuse_latent(spec, feature, why):
    """The one check of everything that knows per-head keys and values
    only."""
    if spec.latent_v_dim:
        raise LatentPoolUnsupported(feature, why)


def spec_for_model(model, *args, **kwargs):
    """The :class:`KVCacheSpec` a model (or its config) describes for
    itself: ``model.cache_spec(max_batch, max_seq, kv_cache_dtype=None,
    page_size=0, n_pages=0)``, which builds it with
    :func:`page_pool_spec`."""
    return model.cache_spec(*args, **kwargs)


def page_pool_spec(max_batch, max_seq, *, n_layer, n_head, head_dim,
                   compute_dtype, n_positions, stacked=False,
                   kv_cache_dtype=None, page_size=0, n_pages=0,
                   latent_v_dim=0, groups=(), **recurrent):
    """A :class:`KVCacheSpec` from a model's own numbers (``n_layer``
    layers with ``n_head`` key/value heads of ``head_dim``) and the
    ``inference.kv_cache_dtype`` knob (None = model compute dtype,
    "bf16"/"f32" = plain storage, a codec name = quantized storage).
    ``page_size`` must divide ``max_seq``; ``n_pages=0`` defaults to
    every row filling its ``max_seq`` span at once, plus the trash
    page. ``latent_v_dim`` > 0: a latent pool (one head, one leaf a
    layer; plain storage only). ``groups``: page layers that are not all
    alike, as ``(name, layers, n_head, head_dim, v_dim, window)`` each
    (plain storage, unstacked; ``n_layer``, ``n_head``, ``head_dim`` are
    then the groups' own: pass those of the first). A full group's pool
    has ``n_pages``; a window group's ``max_batch`` rings and a trash
    page. ``recurrent``: the spec's ``layers`` / ``recurrent_layers`` /
    ``recurrent_leaves``."""
    codec = None
    if kv_cache_dtype is None:
        dtype = compute_dtype
    elif kv_cache_dtype == "bf16":
        dtype = jnp.bfloat16
    elif kv_cache_dtype in ("f32", "fp32"):
        dtype = jnp.float32
    elif kv_cache_dtype in CODECS:
        codec = kv_cache_dtype
        dtype = CODECS[kv_cache_dtype].dtype
    else:
        raise ValueError(
            f"kv_cache_dtype must be None, 'bf16', 'f32', or a codec "
            f"name from {sorted(CODECS)}; got {kv_cache_dtype!r}")
    if latent_v_dim and (int(n_head) != 1 or
                         not 0 < int(latent_v_dim) <= int(head_dim)):
        raise ValueError(
            f"a latent pool has one head whose first latent_v_dim "
            f"entries are the value: got n_head {n_head}, head_dim "
            f"{head_dim}, latent_v_dim {latent_v_dim}")
    if latent_v_dim and codec is not None:
        raise LatentPoolUnsupported(
            f"inference.kv_cache_dtype {kv_cache_dtype!r}",
            "the codecs keep one scale a head vector, and a latent is "
            "one vector of compressed keys and values beside a rotary "
            "key: one scale over all of it is a precision nobody has "
            "measured")
    if max_seq > n_positions:
        raise ValueError(
            f"max seq bucket {max_seq} exceeds the model's n_positions "
            f"{n_positions}")
    page_size, n_pages = int(page_size), int(n_pages)
    if page_size < 1 or max_seq % page_size:
        raise ValueError(
            f"page_size {page_size} must be a positive divisor of "
            f"max_seq {max_seq}")
    if not n_pages:
        # every row can fill its full max_seq span concurrently, plus
        # the reserved trash page.
        n_pages = int(max_batch) * (int(max_seq) // page_size) + 1
    if n_pages < 2:
        raise ValueError(
            f"n_pages must be >= 2 (page 0 is the trash page), "
            f"got {n_pages}")
    if groups:
        groups = _page_groups(groups, int(max_batch), page_size, n_pages)
        if codec is not None or stacked or latent_v_dim:
            raise ValueError(
                "groups of page layers are kept in plain storage, "
                "unstacked, with keys and values of their own: no codec, "
                "no scan_layers, no latent pool")
        recurrent["layers"] = tuple(n for g in groups for n in g.layers)
        n_layer = len(recurrent["layers"])
    return KVCacheSpec(
        n_layer=int(n_layer), max_batch=int(max_batch),
        max_seq=int(max_seq), n_head=int(n_head),
        head_dim=int(head_dim), dtype=dtype, codec=codec,
        stacked=bool(stacked), page_size=page_size,
        n_pages=n_pages, latent_v_dim=int(latent_v_dim),
        groups=tuple(groups), **recurrent)


def _page_groups(groups, max_batch, page_size, n_pages):
    """:class:`PageGroup` s from ``page_pool_spec``'s tuples, each with
    its pool's pages. The window groups share a row's ring, so they
    share a window, which is whole pages (a ring entry is a page)."""
    out = [PageGroup(str(name), tuple(layers), int(n_head), int(head_dim),
                     int(v_dim), int(window))
           for name, layers, n_head, head_dim, v_dim, window in groups]
    windows = {g.window for g in out if g.window}
    if len(windows) > 1 or any(w % page_size for w in windows):
        raise ValueError(
            f"window groups share one ring a row: one window, a whole "
            f"number of pages of {page_size}; got {sorted(windows)}")
    if any(not g.layers or g.n_head < 1 or g.v_dim < 1 or
           g.v_dim > g.head_dim or g.window < 0 for g in out):
        raise ValueError(
            f"a group has layers, heads, and values no wider than its "
            f"keys; got {out}")
    ring = max(windows, default=0) // page_size + 1
    return tuple(dataclasses.replace(
        g, n_pages=max_batch * ring + 1 if g.window else n_pages)
        for g in out)


def payload_shape(spec, group=None, leaf="k"):
    """The shape of one layer's K (or V) pool; of a latent pool's one
    leaf, ``[n_pages, 1, latent_v_dim + rope, page_size]``. ``group``: a
    :class:`PageGroup` of the spec (default: its first), whose ``v``
    leaf may be narrower than its ``k``."""
    g = group or spec.page_groups[0]
    return (g.n_pages, g.n_head, g.v_dim if leaf == "v" else g.head_dim,
            spec.page_size)


def _payload_names(spec):
    """A layer's payload leaves: keys and values, or the latent alone."""
    return ("k",) if spec.latent_v_dim else ("k", "v")


def _layer_leaves(spec, group=None):
    shape = payload_shape(spec, group)
    leaves = {name: jnp.zeros(payload_shape(spec, group, name), spec.dtype)
              for name in _payload_names(spec)}
    if spec.codec is not None:
        # one scale per (position, head): the payload less head_dim
        sshape = shape[:2] + shape[3:]
        leaves["k_scale"] = jnp.zeros(sshape, jnp.float32)
        leaves["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return leaves


def init_kv_cache(spec):
    """Zero-filled cache pytree keyed like the model's params: per-layer
    ``h_<i>`` subtrees (unrolled) or one stacked ``h`` subtree
    (``scan_layers``)."""
    if spec.stacked:
        return {"h": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (spec.n_layer,) + a.shape),
            _layer_leaves(spec))}
    cache = {}
    for group in spec.page_groups:
        layer = _layer_leaves(spec, group)
        for name in group.layers:
            cache[name] = jax.tree_util.tree_map(jnp.array, layer)
    for name in spec.recurrent_layers:
        cache[name] = {leaf: jnp.zeros(shape, dtype)
                       for leaf, shape, dtype in spec.recurrent_leaves}
    return cache


def kv_cache_nbytes(cache):
    return sum(int(leaf.nbytes)
               for leaf in jax.tree_util.tree_leaves(cache))


def cache_dtype_census(cache):
    """``{dtype_str: leaf count}`` over the cache's k/v payload leaves
    (scales excluded) — the decode audit's cache-dtype-hygiene fact."""
    census = {}
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    for path, leaf in flat:
        key = str(getattr(path[-1], "key", path[-1]))
        if key not in ("k", "v"):
            continue
        dt = str(jnp.dtype(leaf.dtype))
        census[dt] = census.get(dt, 0) + 1
    return census


def kv_partition_specs(spec, model_axis="model"):
    """PartitionSpecs sharding the cache's head axis over the TP mesh
    axis — the cache analog of the model's Megatron column-parallel QKV
    (`models/gpt2.py:gpt2_partition_specs`): each TP shard holds the
    heads it computes, so decode attention runs collective-free and the
    row-parallel ``c_proj`` psum GSPMD inserts is the only combine.
    The pool keeps heads on axis 1 (``[n_pages, H, D, page_size]``)."""
    from jax.sharding import PartitionSpec as P
    refuse_latent(spec, "a 'model' mesh axis over the cache's heads",
                  "a latent is one head, and every query head reads all "
                  "of it")
    if spec.groups:
        raise ValueError(
            "a 'model' mesh axis over groups of page layers with heads of "
            "their own has not been built: each group's heads would be "
            "split by the axis")
    lead = (None,) if spec.stacked else ()
    # no trailing None after the sharded head axis: jit keys compiled
    # programs on the exact sharding object, and GSPMD canonicalizes
    # output specs without trailing Nones — a trailing-None input spec
    # would mismatch the pinned output and recompile on the 2nd call.
    payload = scale = P(*lead, None, model_axis)

    def per_layer():
        leaves = {"k": payload, "v": payload}
        if spec.codec is not None:
            leaves["k_scale"] = scale
            leaves["v_scale"] = scale
        return leaves

    if spec.stacked:
        return {"h": per_layer()}
    names = spec.layers or [f"h_{i}" for i in range(spec.n_layer)]
    return {name: per_layer() for name in names}


# ---------------------------------------------------------------------------
# in-jit cache ops (used by models/gpt2.py's cached attention path)
# ---------------------------------------------------------------------------

def _codec_of(layer_cache):
    """Recover the storage codec from the cache leaves themselves (a
    traced pytree can't carry the name): quantized caches are the ones
    with scale leaves, and the payload dtype names the codec."""
    if "k_scale" not in layer_cache:
        return None
    dt = jnp.dtype(layer_cache["k"].dtype)
    for codec in CODECS.values():
        if jnp.dtype(codec.dtype) == dt:
            return codec
    raise ValueError(
        f"quantized KV cache stores dtype {dt} which matches no codec "
        f"in {sorted(CODECS)}")


def _quantize(x, codec):
    """Per-(row, position, head) absmax quantization — the
    ``encode_chunks`` recipe with the head vector as the chunk."""
    codec = get_codec(codec)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = absmax / codec.qmax
    safe = jnp.where(scale > 0.0, scale, 1.0)
    scaled = xf / safe[..., None]
    if codec.integer:
        q = jnp.clip(jnp.round(scaled), -codec.qmax, codec.qmax)
    else:
        q = jnp.clip(scaled, -codec.qmax, codec.qmax)
    return q.astype(codec.dtype), scale


def _dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attention_mask(layer_cache, positions, page_table):
    """The dense path's ``[B, T, S]`` position mask (cache index ``s``
    visible to the query at position ``p`` iff ``s <= p``). Exposed so
    callers running several layers per step (`models/gpt2.py`) can
    compute it ONCE and pass it down — rebuilt per layer it is the
    compiled decode program's only per-layer iota. The pool does not
    carry the sequence length (``shape[-1]`` is ``page_size``); ``S``
    is ``pages_per_row * page_size`` off the page table."""
    S = page_table.shape[-1] * layer_cache["k"].shape[-1]
    return jnp.arange(S)[None, None, :] <= positions[:, :, None]


# ---------------------------------------------------------------------------
# paged pool ops
# ---------------------------------------------------------------------------

def _write_tokens(layer_cache, new, pages, offs):
    """Token ``i``'s ``new[leaf][i]`` (``[H, D]`` payload or ``[H]``
    scale) becomes position ``offs[i]`` of page ``pages[i]`` of each
    leaf of ``layer_cache`` (``[n_pages, H, (D,) page_size]``), one
    token after the other, all leaves in one loop.

    A position is one lane of every tile of its page, so each token
    reads its page's slab, replaces the one lane, and writes the slab
    back: the pool is indexed dynamically on the page axis only, which
    XLA updates in place, in the pool's own tiled layout. (A scatter on
    the position axis makes XLA copy the whole pool out of that layout
    and back; `tests/unit/test_tpu_compile.py` holds the count at 0.)
    """
    hit = jnp.arange(layer_cache["k"].shape[-1]) == offs[:, None]
    new = {name: vals.astype(layer_cache[name].dtype)[..., None]
           for name, vals in new.items()}

    def one(i, leaves):
        def write(buf, vals):
            slab = jax.lax.dynamic_index_in_dim(buf, pages[i], 0)
            slab = jnp.where(hit[i], vals[i], slab)
            return jax.lax.dynamic_update_index_in_dim(buf, slab,
                                                       pages[i], 0)
        return {name: write(buf, new[name])
                for name, buf in leaves.items()}

    return jax.lax.fori_loop(0, offs.shape[0], one, layer_cache)


def _write_chunk(buf, vals, page, off):
    """One row's chunk ``vals`` (``[T, H, (D)]``, contiguous positions
    from ``off``, all inside ``page``) into ``buf``: the chunk goes into
    the page's slab, the slab back into the pool on the page axis."""
    slab = jax.lax.dynamic_index_in_dim(buf, page, 0)
    chunk = jnp.moveaxis(vals, 0, -1).astype(buf.dtype)[None]
    slab = jax.lax.dynamic_update_slice_in_dim(slab, chunk, off, -1)
    return jax.lax.dynamic_update_index_in_dim(buf, slab, page, 0)


def _new_leaves(layer_cache, k_new, v_new):
    """A chunk's keys and values as the pool stores them, under the
    pool's leaf names, position-major: ``[B, T, H, D]`` payloads and,
    for a codec pool (quantized here), ``[B, T, H]`` scales, one per
    (position, head). A latent pool (no ``v`` leaf) takes ``k_new``
    alone."""
    if ("v" in layer_cache) != (v_new is not None):
        raise ValueError(
            f"the pool's leaves are {sorted(layer_cache)}: new values go "
            f"with a v leaf, and a latent pool takes its latents alone")
    if v_new is None:
        return {"k": k_new}
    codec = _codec_of(layer_cache)
    if codec is None:
        return {"k": k_new, "v": v_new}     # cast where they are written
    new = {}
    new["k"], new["k_scale"] = _quantize(k_new, codec)
    new["v"], new["v_scale"] = _quantize(v_new, codec)
    return new


def split_table(page_table, ring_pages):
    """A row's page table as ``(the full groups' entries, the ring's)``
    (`KVCacheSpec.table_width`); the ring's part is empty where no
    group has a window."""
    width = page_table.shape[-1] - ring_pages
    return page_table[..., :width], page_table[..., width:]


def paged_write_kv(layer_cache, k_new, v_new, positions, page_table,
                   ring=False, n_valid=None):
    """Write one chunk's keys/values into the page pool through a
    page table. ``layer_cache`` holds ``[n_pages, H, D, page_size]``
    pool leaves (scales ``[n_pages, H, page_size]``);
    ``page_table`` is ``[B, pages_per_row]`` int32 of
    physical page ids (0 = trash for unallocated slots); positions are
    contiguous per row. Two shapes exist:

    - prefill (``B == 1``): the whole chunk into a single page
      (:func:`_write_chunk`), or a chunk of several whole pages page by
      page — the engine pins ``page_size % prefill_chunk == 0`` or
      ``prefill_chunk % page_size == 0``, so a chunk never straddles a
      page it does not fill.
    - decode (``T == 1``) and speculative verify (``B > 1, T > 1``):
      token by token (:func:`_write_tokens`) — each (row, step) token
      resolves its own (page, slot) through the table, so a verify
      chunk MAY straddle a page boundary. Inactive decode rows sit at
      position 0 with table entry 0 and collide harmlessly on the
      trash page; positions past a row's allocated pages hit table
      entry 0 and land there too (rejected-tail rollback: those writes
      are garbage by construction and never become visible).

    A pool that stores a codec dtype quantizes on the way in, one
    scale per (page, slot, head); the flash kernel's fused dequant
    reads them beside the payload.

    ``ring``: ``page_table`` is the rows' ring (a window group's): a
    position's entry is its page's number modulo the table's width, and
    a chunk of more pages than the ring holds overwrites its own first
    pages with its last, in order. ``n_valid`` (``[B]``): tokens of a
    row past it are padding and go to the trash page (a ring has no
    entry to spare for them: written, a padded page would take the
    place of one the window still reaches).
    """
    with jax.named_scope("ds_kv_write"):
        page_size = layer_cache["k"].shape[-1]
        B, T = positions.shape
        entry = positions // page_size
        if ring:
            entry = entry % page_table.shape[1]
        pages = jnp.take_along_axis(page_table, entry, axis=1)  # [B, T]
        if n_valid is not None:
            pages = jnp.where(jnp.arange(T)[None] < n_valid[:, None],
                              pages, 0)
        offs = positions % page_size
        new = _new_leaves(layer_cache, k_new, v_new)
        if B == 1 and T <= page_size:
            return {name: _write_chunk(layer_cache[name], vals[0],
                                       pages[0, 0], offs[0, 0])
                    for name, vals in new.items()}
        if B == 1:
            # a chunk of several whole pages (it starts on a page
            # boundary: the engine pins prefill_chunk % page_size == 0),
            # page by page
            out = dict(layer_cache)
            for j in range(0, T, page_size):
                for name, vals in new.items():
                    out[name] = _write_chunk(out[name],
                                             vals[0, j:j + page_size],
                                             pages[0, j], 0)
            return out
        return _write_tokens(
            layer_cache,
            {name: vals.reshape((B * T,) + vals.shape[2:])
             for name, vals in new.items()},
            pages.reshape(B * T), offs.reshape(B * T))


def paged_read_kv(layer_cache, page_table, dtype):
    """Gather each row's pages into contiguous ``[B, S, H, D]``
    key/value buffers in compute ``dtype`` (S = pages_per_row *
    page_size) — the dense oracle's view of the pool. Trash /
    unallocated entries gather page 0's garbage, which the position
    mask hides like any stale slot. A latent pool has no values of its
    own: ``(latents, None)``."""
    codec = _codec_of(layer_cache)

    def gather(buf):
        g = jnp.take(buf, page_table, axis=0)   # [B, n_pt, H, (D,) ps]
        g = jnp.moveaxis(g, -1, 2)              # [B, n_pt, ps, H, (D)]
        B, n_pt, ps = g.shape[:3]
        return g.reshape((B, n_pt * ps) + g.shape[3:])

    if "v" not in layer_cache:
        return gather(layer_cache["k"]).astype(dtype), None
    if codec is None:
        return (gather(layer_cache["k"]).astype(dtype),
                gather(layer_cache["v"]).astype(dtype))
    return (_dequantize(gather(layer_cache["k"]),
                        gather(layer_cache["k_scale"]), dtype),
            _dequantize(gather(layer_cache["v"]),
                        gather(layer_cache["v_scale"]), dtype))


def _flash_attend_paged(q, new, layer_cache, positions, page_table,
                        block_k, mesh, scale=None, v_dim=None, window=0,
                        sink=None):
    """A flash decode step straight over the STORAGE pool: the step's
    ``new`` keys and values (:func:`_new_leaves`) go into the pool and
    the rows attend over it in one kernel; returns ``(y, layer_cache)``.
    Quantized pools stream int8/f8 payloads + f32 scales into the
    kernel and never materialize a dequantized copy. The kernel
    fetches each row's live KV blocks out of the pool through the
    scalar-prefetched page table
    (`ops/pallas/flash_decode.py:flash_decode_paged`), puts the row's
    new lane into the last of them and writes that block back — this
    code gathers, transposes and writes nothing, and neither does XLA
    around the call: the pool's tiled layout is the kernel's, and the
    pool it returns is the buffer it was handed. A row whose table
    starts with the trash page holds no request: the kernel runs
    nothing for it, writes nothing, and its output is zeros (the
    scheduler ignores such rows). Under TP the pool shards on its head
    axis 1 (`kv_partition_specs`), in and out, and each shard's kernel
    sees its local heads; the query and the output keep the model's
    ``[B, 1, H, D]`` layout."""
    from deepspeed_tpu.ops.pallas import flash_decode_paged

    def attend(q_, new_, pool_, pos_, table_):
        return flash_decode_paged(q_, new_, pool_, pos_, table_,
                                  block_k=block_k, scale=scale, v_dim=v_dim,
                                  window=window, sink=sink)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        head = P(None, None, "model", None)
        # payloads [.., H, D] and scales [.., H] alike, as are the
        # pool's [n_pages, H, ..] leaves
        heads = {name: P(None, None, "model") for name in new}
        pool = {name: P(None, "model") for name in layer_cache}
        attend = jax.shard_map(
            attend, mesh=mesh,
            in_specs=(head, heads, pool, P(None), P(None, None)),
            out_specs=(head, pool), check_vma=False)
    return attend(q, new, layer_cache, positions[:, 0], page_table)


def _walk_pages(pages_per_row, page_size, limit):
    """Pages to a block of the prefill walk: the largest divisor of the
    table's width whose block holds at most ``limit`` positions."""
    return max(n for n in range(1, pages_per_row + 1)
               if pages_per_row % n == 0 and
               (n == 1 or n * page_size <= limit))


# positions to a block of a prefill chunk's walk over a latent pool: a
# head's float32 scores of a block are [chunk, WALK_BLOCK]
WALK_BLOCK = 1024


def latent_walk_block(pages_per_row, page_size):
    """Positions to a block of the walk over a row of ``pages_per_row``
    pages: a chunk whose last query sits at ``p`` visits ``p // block +
    1`` of them (the engine's counters reckon so too)."""
    return _walk_pages(pages_per_row, page_size, WALK_BLOCK) * page_size


def latent_prefill_attention(q, layer_cache, positions, page_table, *,
                             expand, scale, compute_dtype, impl="dense"):
    """One prompt's chunk of queries over the row's live prefix in a
    latent pool, the chunk's own latents already written: blocks of
    whole pages, gathered through the table one after the other under a
    running max and sum (float32). Only the blocks up to the chunk's
    last position are visited (a ``while`` over ``pos // block + 1``),
    and the mask is the pool's own: cache index ``s`` for the query at
    ``p`` iff ``s <= p``.

    Each block is expanded as it is met: ``expand(latents [S, D]) ->
    (k [S, H, dk], k_shared [S, dr], v [H, dv, S])`` is the caller's
    up-projection (each as the product that makes it lies: keys a
    position a row, values a position a lane) beside the rotary key the
    heads share. ``q`` is ``[1, T, H, dk + dr]``, a head's shared
    entries last; returns ``[1, T, H, dv]``. That costs ``dk + dr +
    dv`` multiply-adds a query-key pair and the block's expansion once
    a chunk; scoring the latents as they lie (the decode step's
    absorbed form: ``D + v_dim`` a pair, nothing expanded) read 5 to 7
    % slower at every prefix on the chip (`PERF.md` section 6, PR 34).

    What takes a block into the running max and sum is ``impl``'s:
    ``"flash"`` the kernel (`ops/pallas/latent_prefill.py`: a head's
    ``[block, chunk]`` scores stay in VMEM, a block on the diagonal is
    computed up to the diagonal), ``"dense"`` plain XLA, the parity
    oracle, whose largest array is the float32 ``[heads, chunk,
    block]`` scores. Same operands, same precision, same order.
    """
    pool = layer_cache["k"]
    page_size = pool.shape[-1]
    _, T, H, _ = q.shape
    S = latent_walk_block(page_table.shape[-1], page_size)
    bp = S // page_size
    pos = positions[0]                                   # [T]
    n_blocks = pos[-1] // S + 1
    flash = impl == "flash"
    # the kernel's tile is keys first: a query to a lane
    qh = jnp.transpose(q[0], (1, 2, 0) if flash else (1, 0, 2))

    def block(i, carry):
        pages = jax.lax.dynamic_slice_in_dim(page_table[0], i * bp, bp)
        lat = jnp.take(pool, pages, axis=0)[:, 0]        # [bp, D, page]
        lat = jnp.moveaxis(lat, -1, 1).reshape(S, -1)    # [S, D]
        lat = lat.astype(compute_dtype)
        kb, shared, vb = expand(lat)        # [S, H, dk], [S, dr], [H, dv, S]
        if flash:
            from deepspeed_tpu.ops.pallas import flash_prefill_latent_block
            return flash_prefill_latent_block(
                qh, kb, shared, vb, carry, pos[0], i * S, scale=scale)
        m_prev, l_prev, acc = carry
        kb = jnp.concatenate(
            [kb, jnp.broadcast_to(shared[:, None], (S, H, shared.shape[1]))],
            -1)
        s = jnp.einsum("htd,shd->hts", qh, kb,
                       preferred_element_type=jnp.float32)
        k_pos = i * S + jnp.arange(S)
        s = jnp.where(k_pos[None, None, :] <= pos[None, :, None],
                      s * scale, jnp.finfo(jnp.float32).min)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + pr.sum(-1, keepdims=True)
        pr = pr.astype(compute_dtype)
        pv = jnp.einsum("hts,hvs->htv", pr, vb,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    # the output's width, from the expansion's shapes alone
    dv = jax.eval_shape(
        expand, jax.ShapeDtypeStruct((S, pool.shape[2]),
                                     compute_dtype))[2].shape[1]
    stat, out = ((H, 1, T), (H, dv, T)) if flash else ((H, T, 1), (H, T, dv))
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full(stat, -jnp.inf, jnp.float32),
         jnp.zeros(stat, jnp.float32), jnp.zeros(out, jnp.float32)))
    y = (acc / jnp.maximum(l, 1e-30)).astype(compute_dtype)
    return jnp.transpose(y, (2, 0, 1) if flash else (1, 0, 2))[None]


def _sink_stats(sink, rows):
    """The running max and sum an online softmax starts from: a sink's
    logit and its weight of 1 in the denominator (``sink`` ``[Hq]``, a
    query head each, against ``rows`` = ``(H, G, ...)``), or nothing."""
    if sink is None:
        return (jnp.full(rows, -jnp.inf, jnp.float32),
                jnp.zeros(rows, jnp.float32))
    m = sink.astype(jnp.float32).reshape(rows[:2] + (1,) * (len(rows) - 2))
    return jnp.broadcast_to(m, rows), jnp.ones(rows, jnp.float32)


def paged_prefill_attention(q, layer_cache, positions, page_table, *, scale,
                            compute_dtype, sink=None):
    """One prompt's chunk of queries over the row's live prefix in a
    pool of per-head keys and values, the chunk's own already written:
    :func:`latent_prefill_attention`'s walk (blocks of whole pages
    through the table, a running max and sum in float32, the blocks up
    to the chunk's last position and no further) with nothing to expand.
    ``q`` ``[1, T, Hq, D]`` over ``H`` key heads of ``D`` and values of
    ``Dv``; returns ``[1, T, Hq, Dv]``. The largest array is a block's
    float32 scores ``[Hq, T, WALK_BLOCK]``: nothing as long as the bucket
    is built. ``sink``: a logit a query head in the denominator."""
    k_pool, v_pool = layer_cache["k"], layer_cache["v"]
    H, D, page_size = k_pool.shape[1:]
    Dv = v_pool.shape[2]
    _, T, Hq, _ = q.shape
    G = Hq // H
    S = latent_walk_block(page_table.shape[-1], page_size)
    bp = S // page_size
    pos = positions[0]
    n_blocks = pos[-1] // S + 1
    # a key head's G query heads share its block: [H, G x T, D]
    qh = jnp.transpose(q[0].reshape(T, H, G, D), (1, 2, 0, 3)).reshape(
        H, G * T, D)
    q_pos = jnp.tile(pos, G)                                # [G x T]
    scale = jnp.asarray(scale, jnp.float32)

    def block(i, carry):
        m_prev, l_prev, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(page_table[0], i * bp, bp)

        def rows(pool):             # [bp, H, d, page] -> [H, d, S]
            blk = jnp.take(pool, pages, axis=0)
            return jnp.transpose(blk, (1, 2, 0, 3)).reshape(
                H, pool.shape[2], S).astype(compute_dtype)

        s = jnp.einsum("hrd,hds->hrs", qh, rows(k_pool),
                       preferred_element_type=jnp.float32)
        k_pos = i * S + jnp.arange(S)
        s = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None],
                      s * scale, jnp.finfo(jnp.float32).min)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + pr.sum(-1, keepdims=True)
        pv = jnp.einsum("hrs,hvs->hrv", pr.astype(compute_dtype),
                        rows(v_pool), preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    m0, l0 = _sink_stats(sink, (H, G, T, 1))
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (m0.reshape(H, G * T, 1), l0.reshape(H, G * T, 1),
         jnp.zeros((H, G * T, Dv), jnp.float32)))
    y = (acc / jnp.maximum(l, 1e-30)).astype(compute_dtype)
    return jnp.transpose(y.reshape(H, G, T, Dv), (2, 0, 1, 3)).reshape(
        1, T, Hq, Dv)


def _grouped_softmax(q, k, v, seen, scale, sink, compute_dtype):
    """``q`` ``[N, T, Hq, D]`` over ``k`` ``[N, S, H, D]`` and ``v``
    ``[N, S, H, Dv]`` under ``seen`` ``[N, T, S]``, a query head over key
    head ``h // G``; the scores and the softmax float32, a ``sink``'s
    weight in the denominator and in no value. ``[N, T, Hq, Dv]``."""
    N, T, Hq, D = q.shape
    H = k.shape[2]
    qg = q.reshape(N, T, H, Hq // H, D)
    s = jnp.einsum("nthgd,nshd->nhgts", qg, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(seen[:, None, None], s * jnp.asarray(scale, jnp.float32),
                  jnp.finfo(jnp.float32).min)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32).reshape(H, Hq // H, 1, 1)
        m = jnp.maximum(m, b)
    pr = jnp.exp(s - m)
    l = pr.sum(-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(b - m)
    pr = (pr / jnp.maximum(l, 1e-30)).astype(compute_dtype)
    y = jnp.einsum("nhgts,nshd->nthgd", pr, v)
    return y.reshape(N, T, Hq, v.shape[-1])


def band_kernel_takes(impl, window):
    """Whether a window layer's prefill chunk goes through the band's
    kernel (`ops/pallas/window_prefill.py`): under ``impl="flash"``, where
    the window is longer than the kernel's smallest query block. A block
    of ``bq`` queries computes ``bq + window`` keys a query where XLA's
    band computes ``2 x window``, so at ``window <= bq`` the kernel saves
    no work and XLA's score tiles are small enough to stay on the chip.
    On a v5e, a chunk of 1,024, device time a layer a call (my chip runs,
    PR 52): window 512 under 72 query heads of 128 (Laguna): XLA 2.36 ms,
    the kernel 0.36 (0.28 its own, the rest the keys' gather); window 128
    under 64 heads of 192 over values of 128 with a sink (MiMo): XLA
    0.171 ms, the kernel 0.42 at 128 queries a block (0.32 its own) and
    0.28 at 256. The engine's counters ask here too."""
    from deepspeed_tpu.ops.pallas.window_prefill import QUERY_BLOCK
    return impl == "flash" and int(window) > QUERY_BLOCK


def chunk_kernel_takes(impl, rows, tokens, group, head_dim, dtype,
                       pool_dtype, quantised=False, mesh=None):
    """Whether a chunk over a pool of per-head pages (no group, no
    latent) goes through the chunk's kernel
    (`ops/pallas/chunk_prefill.py`) and not the dense arm: under
    ``impl="flash"``, one row (a prompt's chunk: speculative verify
    brings several), ``tokens`` whole query blocks of the kernel's, the
    queries and the pool both bfloat16 (the MXU's operands as stored: a
    float32 model's chunk and a codec's pool stay the dense arm's, which
    widens or dequantises its gathered view), no TP ``mesh`` (a sharded
    pool's chunk is GSPMD's to cut), and a key head's ``group`` query
    heads of ``head_dim`` whole lane tiles of the model's ``[T, Hq x
    D]``, where the kernel cuts its blocks. Decided from the call's
    shapes, dtypes and pool alone. On a v5e, a layer a call (my chip
    runs, PR 58; `PERF.md` section 6 has every geometry): 32 query heads
    over 8 key heads of 64, a chunk of 1,024 in a bucket of 9,216 (LFM2):
    XLA's dense arm 5.2 ms whatever the prefix, the kernel 0.20 ms at a
    prefix of 1,024, 0.45 at 3,072, 0.71 at 5,120. The chat cell's
    float32 chunk of 64 stays XLA's, by its dtype and by the query block
    (and 16 heads of 64 are no whole lane tile a key head): section 6
    has what the kernel read there. The engine's counters ask here too."""
    from deepspeed_tpu.ops.pallas.chunk_prefill import LANES, QUERY_BLOCK
    return (impl == "flash" and rows == 1 and tokens > 0 and
            tokens % QUERY_BLOCK == 0 and
            jnp.dtype(dtype) == jnp.dtype(pool_dtype) == jnp.bfloat16 and
            not quantised and mesh is None and
            (group * head_dim) % LANES == 0)


def window_prefill_attention(q, k_new, v_new, layer_cache, positions, ring,
                             *, window, scale, compute_dtype, sink=None,
                             n_valid=None, impl="dense"):
    """One prompt's chunk over its own keys and the ``window`` positions
    before it, which the row's ring still holds (read here, **before**
    the chunk is written over them): a band. Query ``t`` sees ``j`` iff
    ``0 <= t - j < window``. ``q`` ``[1, T, Hq, D]``, ``k_new`` /
    ``v_new`` ``[1, T, H, D | Dv]``, ``ring`` ``[1, ring_pages]``;
    returns ``[1, T, Hq, Dv]``.

    What attends is ``impl``'s. ``"flash"``, where the window is long
    enough for it (:func:`band_kernel_takes`): the kernel
    (`ops/pallas/window_prefill.py`: a key head's query heads over the
    key blocks their band admits, the scores in VMEM; a block of queries
    wholly behind the chunk's ``n_valid`` real tokens comes back zero).
    ``"dense"``, or a short window: plain XLA, the parity oracle: the
    chunk's queries in blocks of ``window`` (of the whole chunk where
    ``window`` does not divide it), a block over its own keys and the
    ``window`` before them, so the scores are ``[blocks, Hq, window, 2 x
    window]`` whatever the row's length. Same operands, same
    precision."""
    _, T, Hq, D = q.shape
    W = int(window)
    held_k, held_v = paged_read_kv(layer_cache, ring, compute_dtype)
    c0 = positions[0, 0]
    # what the ring holds of [c0 - W, c0): position p lies at p modulo
    # the ring's span (before the prompt's start: masked below)
    before = (c0 - W + jnp.arange(W)) % held_k.shape[1]
    k_before, v_before = held_k[0][before], held_v[0][before]
    k_new = k_new[0].astype(compute_dtype)
    v_new = v_new[0].astype(compute_dtype)
    if band_kernel_takes(impl, W):
        from deepspeed_tpu.ops.pallas import window_prefill_band
        return window_prefill_band(
            q[0], k_before, v_before, k_new, v_new, c0,
            T if n_valid is None else jnp.reshape(n_valid, ()),
            window=W, scale=scale, sink=sink)[None]
    k_ext = jnp.concatenate([k_before, k_new])
    v_ext = jnp.concatenate([v_before, v_new])
    bq = W if T % W == 0 else T
    nb = T // bq
    span = lambda a: jnp.stack([a[j * bq:j * bq + W + bq] for j in range(nb)])
    r = jnp.arange(bq)[:, None]
    c = jnp.arange(W + bq)[None, :]
    # query r of block j is at c0 + j bq + r, key c at c0 - W + j bq + c
    seen = (c > r) & (c <= r + W)
    k_pos = c0 - W + jnp.arange(nb)[:, None, None] * bq + c[None]
    seen = seen[None] & (k_pos >= 0)
    y = _grouped_softmax(q[0].reshape(nb, bq, Hq, D), span(k_ext),
                         span(v_ext), seen, scale, sink, compute_dtype)
    return y.reshape(1, T, Hq, y.shape[-1])


def _dense_attend(q, layer_cache, positions, page_table, window, scale,
                  sink, compute_dtype):
    """The dense oracle of a group's decode step (and of a full layer's
    chunk), the step's keys already written: the row's gathered pages
    under the mask by position. Of a ring, entry ``j`` holds the largest
    position ``<= p`` that is ``j`` modulo the ring's span, seen iff it
    is no further back than the window."""
    k, v = paged_read_kv(layer_cache, page_table, compute_dtype)
    S = k.shape[1]
    p = positions[:, :, None]                               # [B, T, 1]
    at = jnp.arange(S)[None, None, :]
    if window:
        held = p - (p - at) % S
        seen = (held >= 0) & (p - held < window)
    else:
        seen = at <= p
    return _grouped_softmax(q, k, v, seen, scale, sink, compute_dtype)


def cached_attention(q, k_new, v_new, layer_cache, positions,
                     compute_dtype, page_table, impl="dense",
                     block_k=128, mesh=None, mask=None, scale=None,
                     v_dim=None, expand=None, window=0, sink=None,
                     n_valid=None, walk=False):
    """Write this chunk's k/v, then attend over the whole cache row.

    ``q``/``k_new``/``v_new``: ``[B, T, H, D]`` (T = 1 for a decode
    step, ``prefill_chunk`` for a prefill chunk); with grouped-query
    attention ``q`` has a multiple ``G`` of the ``H`` heads of
    ``k_new``/``v_new`` and of the pool, and query head ``h`` attends
    over key head ``h // G``. ``scale`` multiplies the scores (``None``:
    ``1 / sqrt(D)``). ``positions``:
    ``[B, T]`` absolute token positions, contiguous per row;
    ``page_table``: ``[B, pages_per_row]`` int32, the rows' physical
    pages. Returns ``(y [B, T, H, D], updated layer_cache)``. Writes
    route through :func:`paged_write_kv`, but for a flash decode step.

    ``impl="flash"`` routes decode steps (T == 1) through the Pallas
    split-K kernel (`ops/pallas/flash_decode.py`): online-softmax over
    ``block_k``-sized cache blocks, only the blocks a row has filled,
    and quantized storage dequantized IN-kernel (scales as a side
    input — no fp32 cache copy). **That kernel also makes the step's
    write**: a live row's new key and value go into the block holding
    its position, which the kernel has fetched anyway, and the block
    goes back to the pool; rows without a request write nothing (the
    48-slab loop of :func:`_write_tokens` wrote all of them, whatever
    they held: 56 % of the chat cell's decode step, `PERF.md` section
    6, PR 33). Prefill chunks and speculative verify (T > 1) are
    written by :func:`paged_write_kv`. **One prompt's chunk** (``B ==
    1``) then attends in one call of the chunk's kernel
    (`ops/pallas/chunk_prefill.py`: a key head's query heads over the
    row's live key blocks, fetched from the pool's pages where they lie
    through the page table, the scores in VMEM; nothing past the chunk's
    last position is read) where :func:`chunk_kernel_takes` the call:
    whole query blocks of bfloat16 queries over a bfloat16 pool in plain
    storage, no ``mesh``, a key head's query heads whole lane tiles.
    Everything else (``impl="dense"``, speculative verify, a codec's or
    a sharded pool, a float32 model's chunk, a chunk under the kernel's
    query block) takes the dense path over :func:`paged_read_kv`'s
    gathered view, which stays the parity oracle, as does the dense
    decode step (a latent pool's chunk has a kernel of its own: below).
    ``mesh``: a TP mesh whose ``model`` axis shards
    the pool's head dim — the flash call then runs under ``shard_map``
    per local head shard. ``mask``: a precomputed
    :func:`attention_mask` (dense path only) so multi-layer callers
    hoist it out of the per-layer body.

    The mask admits cache index ``s`` for the query at position ``p``
    iff ``s <= p`` — the cached generalization of the training path's
    ``tril(T, T)``: within a prefill chunk it reproduces the triangle,
    across chunks it exposes exactly the already-written prefix, and
    for padded chunk tails / recycled-page remnants it hides everything
    until a real token overwrites the slot. Pages only change where
    bytes live, never what the mask admits.

    **A latent pool** (no ``v`` leaf; ``v_new`` None, ``v_dim`` the
    values' width, ``scale`` given): ``k_new`` is the chunk's latents
    ``[B, T, 1, D]``. A decode step takes every head's query in the
    latent's own space (the absorbed form), runs the same kernel
    (which reads a block once for keys and values) or the dense oracle
    below, and ``y`` comes back ``[B, 1, Hq, v_dim]``. One prompt's
    chunk (``B == 1``, ``T > 1``) is written by :func:`paged_write_kv`
    and attends by :func:`latent_prefill_attention`'s walk over the
    row's live blocks through the caller's ``expand``, with ``q`` of
    the expanded width; a block is taken in by the prefill kernel
    (`ops/pallas/latent_prefill.py`) under ``impl="flash"`` and by
    plain XLA, the parity oracle, under ``impl="dense"``.

    **A group of page layers with a window, a sink or values narrower
    than its keys** (``walk`` True; `models/mimo_v2.py`; ``scale``
    given). ``window`` > 0: ``page_table`` is the rows' ring, the decode
    kernel walks the window's blocks and no others, and one prompt's
    chunk attends by :func:`window_prefill_attention` (a band over the
    ring's last ``window`` positions, read before the chunk is written,
    ``n_valid`` of whose tokens are real). ``window`` 0: the chunk is
    written and attends by :func:`paged_prefill_attention`'s walk over
    the row's live blocks; nothing bucket-long is built. ``sink``
    ``[Hq]``: a learned logit a query head in every softmax's
    denominator. ``impl="dense"`` decodes by :func:`_dense_attend`, the
    parity oracle. Each program under a scope of its own:
    ``ds_attn_prefill_window`` / ``ds_attn_prefill_full``, a decode step
    ``ds_attn_decode_window`` / ``ds_attn_decode_full`` around the
    kernel's ``ds_flash_decode_paged``.
    """
    if walk:
        return _grouped_attention(
            q, k_new, v_new, layer_cache, positions, compute_dtype,
            page_table, impl, block_k, scale, window, sink, n_valid)
    if window or sink is not None:
        raise ValueError("a window or a sink goes with walk=True")
    latent = "v" not in layer_cache
    if latent and (v_new is not None or v_dim is None or scale is None):
        raise ValueError(
            "a latent pool takes the chunk's latents alone (v_new None) "
            "with the values' width v_dim and the scores' scale")
    # a latent pool's call lies under its caller's ds_mla_*_attn
    with contextlib.nullcontext() if latent else \
            jax.named_scope(plain_scope(q.shape[1])):
        if impl == "flash" and q.shape[1] == 1:
            y, layer_cache = _flash_attend_paged(
                q, _new_leaves(layer_cache, k_new, v_new), layer_cache,
                positions, page_table, block_k, mesh, scale, v_dim)
            return y.astype(compute_dtype), layer_cache
        layer_cache = paged_write_kv(layer_cache, k_new, v_new, positions,
                                     page_table)
        if latent and q.shape[1] > 1:
            if q.shape[0] != 1 or expand is None:
                raise ValueError(
                    "a latent pool attends several tokens at once only as "
                    "one prompt's chunk (one row), through expand")
            return latent_prefill_attention(
                q, layer_cache, positions, page_table, expand=expand,
                scale=scale, compute_dtype=compute_dtype,
                impl=impl), layer_cache
        B, T, Hq, D = q.shape
        H = layer_cache["k"].shape[1]
        if not latent and chunk_kernel_takes(
                impl, B, T, Hq // H, D, q.dtype, layer_cache["k"].dtype,
                _codec_of(layer_cache) is not None, mesh):
            from deepspeed_tpu.ops.pallas import flash_prefill_paged
            y = flash_prefill_paged(
                q[0], layer_cache["k"], layer_cache["v"], page_table[0],
                positions[0, 0], scale=_dense_scale(scale, D, compute_dtype))
            return y[None].astype(compute_dtype), layer_cache
        if mask is None:
            mask = attention_mask(layer_cache, positions, page_table)
        k_full, v_full = paged_read_kv(layer_cache, page_table,
                                       compute_dtype)
        if latent:
            v_full = k_full[..., :v_dim]
        if scale is None:
            scale = 1.0 / jnp.sqrt(jnp.asarray(D, compute_dtype))
        else:
            # a latent's scale (0.1447) is no bfloat16 number
            scale = jnp.asarray(scale,
                                jnp.float32 if latent else compute_dtype)
        if Hq == H:
            att = jnp.einsum("bthd,bshd->bhts", q, k_full) * scale
            att = jnp.where(mask[:, None], att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att.astype(jnp.float32),
                                 axis=-1).astype(compute_dtype)
            return jnp.einsum("bhts,bshd->bthd", att, v_full), layer_cache
        # grouped queries: the G query heads of a group share a key head
        # (scores kept in float32 from the product to the softmax)
        qg = q.reshape(B, T, H, Hq // H, D)
        att = jnp.einsum("bthgd,bshd->bhgts", qg, k_full,
                         preferred_element_type=jnp.float32)
        att = jnp.where(mask[:, None, None],
                        att * scale.astype(jnp.float32),
                        jnp.finfo(jnp.float32).min)
        att = jax.nn.softmax(att, axis=-1).astype(compute_dtype)
        y = jnp.einsum("bhgts,bshd->bthgd", att, v_full)
        return y.reshape(B, T, Hq, v_full.shape[-1]), layer_cache


def _dense_scale(scale, head_dim, compute_dtype):
    """The scores' scale as the dense arm's grouped path takes it, a
    Python number for a kernel: ``scale`` (``None``: ``1 / sqrt(D)``)
    rounded to ``compute_dtype`` and widened to float32."""
    dt = np.dtype(compute_dtype)
    if scale is None:
        return float((1.0 / np.sqrt(np.asarray(head_dim, dt))).astype(dt))
    return float(np.asarray(scale, dt))


def plain_scope(tokens):
    """The scope of :func:`cached_attention` outside page groups and
    latents, by the tokens a row brings: a decode step or a chunk."""
    return "ds_attn_decode_plain" if tokens == 1 else "ds_attn_prefill_plain"


def _grouped_attention(q, k_new, v_new, layer_cache, positions,
                       compute_dtype, page_table, impl, block_k, scale,
                       window, sink, n_valid):
    """:func:`cached_attention` under ``walk``: its docstring's last
    part."""
    kind = "window" if window else "full"
    B, T = positions.shape
    if T == 1:
        with jax.named_scope(f"ds_attn_decode_{kind}"):
            if impl == "flash":
                y, layer_cache = _flash_attend_paged(
                    q, _new_leaves(layer_cache, k_new, v_new), layer_cache,
                    positions, page_table, block_k, None, scale,
                    window=window, sink=sink)
                return y.astype(compute_dtype), layer_cache
            layer_cache = paged_write_kv(layer_cache, k_new, v_new,
                                         positions, page_table,
                                         ring=bool(window))
            return _dense_attend(q, layer_cache, positions, page_table,
                                 window, scale, sink,
                                 compute_dtype), layer_cache
    if B != 1:
        raise ValueError(
            "a group of page layers attends several tokens at once only "
            "as one prompt's chunk (one row)")
    with jax.named_scope(f"ds_attn_prefill_{kind}"):
        if window:
            y = window_prefill_attention(
                q, k_new, v_new, layer_cache, positions, page_table,
                window=window, scale=scale, compute_dtype=compute_dtype,
                sink=sink, n_valid=n_valid, impl=impl)
            return y, paged_write_kv(layer_cache, k_new, v_new, positions,
                                     page_table, ring=True, n_valid=n_valid)
        layer_cache = paged_write_kv(layer_cache, k_new, v_new, positions,
                                     page_table)
        return paged_prefill_attention(
            q, layer_cache, positions, page_table, scale=scale,
            compute_dtype=compute_dtype, sink=sink), layer_cache
