"""InferenceEngine: the two compiled programs of the serving path.

Exactly two jits, compiled once each, reused for the whole serve
(three with speculative decoding — see `inference/speculative.py`,
which swaps the decode program for a draft/verify pair under the same
never-recompile discipline):

- **prefill** — one chunk of one prompt: ``[1, prefill_chunk]`` tokens
  at explicit positions, written into the pages the prompt's page
  table names (plain data, so any request reuses the same program).
  Long prompts are a host loop over same-shaped chunks — prompt length
  never reaches a jit boundary, so it can't recompile the loop and a
  long prompt never forces a fresh XLA program while decodes wait.
- **decode** — one token for every row at once: ``[max_batch]`` tokens
  at per-row positions over the full cache. Inactive rows compute
  garbage at position 0 and the scheduler ignores them; their writes
  land on the trash page.

Everything shape-varying (number of live requests, prompt lengths, per
-request sequence budgets a.k.a. ``seq_buckets``) is host-side
bookkeeping padded to these two static shapes, which is the whole
recompile contract: :meth:`compile_counts` must read ``{"prefill": 1,
"decode": 1}`` from warmup to drain, and :meth:`recompile_findings`
turns any growth into the PR 4 detector's error finding.

The KV cache is one page pool a layer (`inference/cache.py`): both
programs take fixed-shape int32 page tables as plain data, so page
allocation, prefix sharing and host-tier park/resume
(`inference/paging.py`) are admission-time metadata under the SAME
2-compile contract — the pool shape and table shape never change, only
their contents. (``kv_layout`` chose between this pool and a per-row
ring buffer until PR 28; the key is still read, and anything but
``"paged"`` is refused.)

**Where the weights lie** (ISSUE 54). A weight nobody placed lies in
the default (row-major) format, a jitted program's parameters take the
format of the arrays it is handed, and the chip's compiler wants a
projection declared ``[C, H * D]`` the other way round: it re-laid each
one out in front of its matmul on every call. So the engine asks the
compiler, once as it is built, which format each weight of the decode
program should lie in (:func:`asked_weight_formats`) and places the
leaves that lie otherwise (``InferenceEngine._place_weights``); the two
jits stay what they are and take the formats of the committed arrays
they are handed. No option, no list of weights, no model's name: a
model whose weights lie right pays the question and nothing after, and
weights on the host's CPU, whose compiler keeps the default layout, are
not asked about (:func:`_askable`).

With a mesh whose ``model`` axis is >1 the engine places params with
the model's Megatron PartitionSpecs (``model.partition_specs``;
`models/gpt2.py:gpt2_partition_specs` — the
`parallel/tensor_parallel.py` layout) and
the cache with head-sharded specs (`cache.kv_partition_specs`), so
decode matmuls and attention run tensor-parallel with GSPMD inserting
the row-parallel psums.

**The model's side of the seam** (ISSUE 31; nine models answer it:
`models/gpt2.py:GPT2LMHead` in its own file, and the eight served
decoders, `granite_hybrid`, `mla_moe`, `nemotron_h`, `qwen3_next`,
`mimo_v2`, `laguna`, `ling_hybrid` and `lfm2_moe`, through one mixin,
`models/blocks.py:ServedLM`; the engine asks nothing else of a model):

- ``model.cache_spec(max_batch, max_seq, kv_cache_dtype=None,
  page_size=0, n_pages=0)`` -> the :class:`~deepspeed_tpu.inference.
  cache.KVCacheSpec` the model needs: its page pools (layers, key/value
  heads, head size) and, for a layer that keeps a state instead of keys
  and values, per-slot recurrent leaves;
- ``model.serve_apply(params, cache, tokens [B, T], positions [B, T],
  page_table [B, pages_per_row], slots [B], n_valid [B], attn_impl=,
  attn_block_k=, attn_mesh=)`` -> ``(logits [B, vocab] at each row's
  last real token, cache)``. ``slots`` are the rows' batch slots (whose
  recurrent leaves they own) and ``n_valid`` how many of a row's ``T``
  tokens are real: a prefill chunk is one row of ``prefill_chunk``
  tokens, the last chunk's tail padding; a decode step is every row
  with one token, ``n_valid`` 0 where the slot holds no request;
- optionally ``model.serve_counters``, names of int32 scalars that
  ``serve_apply`` then returns third, as a dict (the expert layers'
  counters by name, `models/blocks.py:summed_counters`, and what a
  model adds of its own): a decode step carries them home behind its
  tokens and puts them on its ``decode`` span;
- ``model.partition_specs(params)`` where a ``model`` mesh axis is
  wanted.

A model with recurrent leaves is served with the prefix cache off, and
what cannot carry a state yet (an explicit ``prefix_cache``,
speculative decoding, a tier, a ``model`` axis, page gathers for
park/resume) refuses it when the engine is built
(:class:`~deepspeed_tpu.inference.cache.RecurrentStateUnsupported`).
A model whose pool holds latents (one leaf a layer, one head) is
refused by what knows per-head keys and values: an int8 / fp8 pool, a
``model`` axis, speculative decoding, a tier
(:class:`~deepspeed_tpu.inference.cache.LatentPoolUnsupported`); its
pages are shared, parked and resumed like any others.
A model whose cache is both (`models/ling_hybrid.py`: a latent pool
beside recurrent leaves) is refused by every feature of either list,
and by one that is on both (a tier, a ``model`` axis, speculative
decoding) with both reasons in one error
(:class:`~deepspeed_tpu.inference.cache.LatentAndRecurrentUnsupported`).
A model whose page layers are not all alike lists them as groups in its
``cache_spec`` (`inference/cache.py:PageGroup`: heads, key and value
widths, window); no knob says so. Where a group has a window a row's
``page_table`` is ``spec.table_width`` wide, the full groups' entries
then the row's ring, and ``serve_apply`` takes it apart
(`cache.split_table`). Such a model is served with the prefix cache
off, and an explicit ``prefix_cache``, speculative decoding, a tier, a
``model`` axis and page gathers refuse it
(:class:`~deepspeed_tpu.inference.cache.WindowRingUnsupported`).
"""

import copy

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from deepspeed_tpu.analysis.audit import donated_jit
from deepspeed_tpu.inference.cache import (
    cache_dtype_census,
    init_kv_cache,
    kv_cache_nbytes,
    kv_partition_specs,
    refuse_recurrent,
    refuse_recurrent_or_latent,
    refuse_window_ring,
)
from deepspeed_tpu.inference.paging import TRASH_PAGE
from deepspeed_tpu.telemetry import programs
from deepspeed_tpu.telemetry.spans import Span, enclosing_attr
from deepspeed_tpu.utils.logging import logger

DEFAULT_MAX_BATCH = 8
DEFAULT_SEQ_BUCKETS = (128, 512)
DEFAULT_PREFILL_CHUNK = 32
DEFAULT_MAX_NEW_TOKENS = 64
DEFAULT_ATTENTION_BLOCK_K = 128
DEFAULT_HOST_PARK_THRESHOLD = 0.25


def _cfg_get(config, key, default):
    if config is None:
        return default
    if isinstance(config, dict):
        v = config.get(key, default)
    else:
        v = getattr(config, key, default)
    return default if v is None else v


def asked_weight_formats(fn, args, donate_argnums=()):
    """The ``Format`` the compiler would have each leaf of ``args[0]``
    (a program's weights) lie in, left the choice: ``fn`` lowered and
    compiled for ``args`` (arrays or shapes; every other argument as it
    is handed in) with the weights as ``Format(Layout.AUTO, <the leaf's
    sharding>)``, and the weights' part of the executable's
    ``input_formats``. The executable is dropped: it is the question,
    not the program. A projection the program multiplies by comes back
    in the order its matmul reads, where a weight in the default format
    costs a re-layout ``copy`` on every call."""
    weights = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args[0])
    auto = jax.tree_util.tree_map(
        lambda x: Format(Layout.AUTO, x.sharding), weights)
    asked = jax.jit(
        fn, in_shardings=(auto,) + (None,) * (len(args) - 1),
        donate_argnums=donate_argnums).lower(weights, *args[1:]).compile()
    return asked.input_formats[0][0]


def _askable(leaf):
    """Whether the compiler is asked where ``leaf`` should lie: a device
    array whose backend names its layout, on an accelerator. XLA's CPU
    compiler keeps a parameter in the default layout whatever reads it
    (every leaf of the seven served models' toy programs came back so),
    so for weights on the host's CPU the question would cost a lowering
    and a compile of the decode program for an answer known beforehand."""
    return (isinstance(leaf, jax.Array) and leaf.format.layout is not None
            and all(d.platform != "cpu" for d in leaf.devices()))


def _committed(x):
    """``x`` committed to where it lies; no bytes move."""
    return jax.device_put(x, x.sharding)


class InferenceEngine:
    """Jitted autoregressive decode over a model that answers the
    serving protocol (the module docstring).

    ``model`` is e.g. a :class:`~deepspeed_tpu.models.gpt2.GPT2LMHead`
    (unrolled or ``scan_layers``) or a :class:`~deepspeed_tpu.models.
    granite_hybrid.GraniteHybridLM`; ``params`` its param tree (matching
    layout). ``config`` is the validated ``inference`` block
    (`runtime/config.py:InferenceConfig`) or a plain dict with the same
    keys; ``session`` an optional
    :class:`~deepspeed_tpu.telemetry.session.TelemetrySession` the
    scheduler emits ``decode_step`` events through. :meth:`prefill` and
    :meth:`decode` open their own spans (``prefill``; ``decode`` with
    ``upload``, ``dispatch``, ``wait_tokens``), which
    land in the process-wide span ring with or without a session and
    nest under the scheduler's ``serve/step``.
    """

    def __init__(self, model, params, config=None, mesh=None,
                 session=None):
        # the construction on the span ring, kept past any window:
        # ``setup/engine`` with the placing of the parameters and the
        # pool's allocation inside it
        with Span("setup/engine"):
            self._build(model, params, config, mesh, session)

    def _build(self, model, params, config, mesh, session):
        self.model = model
        self.max_batch = int(_cfg_get(config, "max_batch",
                                      DEFAULT_MAX_BATCH))
        buckets = _cfg_get(config, "seq_buckets", DEFAULT_SEQ_BUCKETS)
        self.seq_buckets = tuple(sorted(int(b) for b in buckets))
        self.prefill_chunk = int(_cfg_get(config, "prefill_chunk",
                                          DEFAULT_PREFILL_CHUNK))
        self.kv_cache_dtype = _cfg_get(config, "kv_cache_dtype", None)
        self.max_new_tokens = int(_cfg_get(config, "max_new_tokens",
                                           DEFAULT_MAX_NEW_TOKENS))
        self.attention_impl = str(_cfg_get(config, "attention_impl",
                                           "dense"))
        self.attention_block_k = int(_cfg_get(config, "attention_block_k",
                                              DEFAULT_ATTENTION_BLOCK_K))
        self.temperature = float(_cfg_get(config, "temperature", 0.0))
        self.top_k = int(_cfg_get(config, "top_k", 0))
        self.top_p = float(_cfg_get(config, "top_p", 1.0))
        self.sampling_seed = int(_cfg_get(config, "sampling_seed", 0))
        kv_layout = _cfg_get(config, "kv_layout", "paged")
        if kv_layout != "paged":
            raise ValueError(
                f"inference.kv_layout {kv_layout!r}: the paged pool is "
                f"the only KV layout since PR 28 (the ring layout and "
                f"the switch are gone); drop the key or set 'paged'")
        self.page_size = int(_cfg_get(config, "page_size", 0))
        self.n_pages = int(_cfg_get(config, "n_pages", 0))
        prefix_cache = _cfg_get(config, "prefix_cache", None)
        self.host_park_threshold = float(_cfg_get(
            config, "host_park_threshold", DEFAULT_HOST_PARK_THRESHOLD))
        # disaggregated serving (ISSUE 20): a tiered engine runs ONE of
        # the two programs — "prefill" tier writes paged KV and never
        # decodes, "decode" tier resumes handed-off pages and never
        # prefills. The pin is host-side (calling the other program
        # raises), so each tier's compile_counts() holds exactly one
        # entry warmup-to-drain and the other stays at zero.
        tier = _cfg_get(config, "tier", None)
        self.tier = str(tier) if tier else None
        if self.tier not in (None, "prefill", "decode"):
            raise ValueError(
                f"inference tier must be 'prefill' or 'decode', got "
                f"{self.tier!r}")
        if self.attention_impl not in ("dense", "flash"):
            raise ValueError(
                f"inference.attention.impl must be 'dense' or 'flash', "
                f"got {self.attention_impl!r}")
        if not 0.0 <= self.host_park_threshold < 1.0:
            raise ValueError(
                f"host_park_threshold must be in [0, 1), got "
                f"{self.host_park_threshold}")
        if self.temperature < 0.0:
            raise ValueError(f"sampling temperature must be >= 0, got "
                             f"{self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got "
                             f"{self.top_p}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got "
                             f"{self.max_batch}")
        if not self.seq_buckets or min(self.seq_buckets) < 1:
            raise ValueError(f"seq_buckets must be non-empty positive "
                             f"ints, got {self.seq_buckets}")
        if self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{self.prefill_chunk}")
        for b in self.seq_buckets:
            if b % self.prefill_chunk:
                # buckets gate how far a row may fill; chunk-aligned
                # buckets keep padded prefill writes inside the buffer.
                raise ValueError(
                    f"every seq bucket must be a multiple of "
                    f"prefill_chunk={self.prefill_chunk}; got bucket {b}")
        self.max_seq = max(self.seq_buckets)
        # flash block size clamps to the cache length and must tile it
        # (the kernel's grid is max_seq / block_k blocks per row).
        self.attention_block_k = min(self.attention_block_k, self.max_seq)
        if self.attention_block_k < 1 or \
                self.max_seq % self.attention_block_k:
            raise ValueError(
                f"attention block_k {self.attention_block_k} must be a "
                f"positive divisor of max_seq {self.max_seq}")
        if not self.page_size:
            # auto: two prefill chunks per page — fine-grained enough
            # for the bytes/session win, coarse enough that page
            # tables stay short.
            self.page_size = min(2 * self.prefill_chunk, self.max_seq)
        if self.page_size % self.prefill_chunk and \
                self.prefill_chunk % self.page_size:
            # a prefill chunk lands inside ONE page (a single
            # dynamic_update_slice) or covers whole pages.
            raise ValueError(
                f"page_size {self.page_size} must be a multiple of "
                f"prefill_chunk {self.prefill_chunk} (or divide it)")
        if self.max_seq % self.page_size:
            raise ValueError(
                f"page_size {self.page_size} must divide max_seq "
                f"{self.max_seq}")
        # a flash KV block must not straddle a page boundary
        self.attention_block_k = min(self.attention_block_k,
                                     self.page_size)
        if self.page_size % self.attention_block_k:
            raise ValueError(
                f"attention block_k {self.attention_block_k} must "
                f"divide page_size {self.page_size}")
        self.spec = model.cache_spec(self.max_batch, self.max_seq,
                                     self.kv_cache_dtype,
                                     page_size=self.page_size,
                                     n_pages=self.n_pages)
        self.n_pages = self.spec.n_pages
        self.pages_per_row = self.spec.pages_per_row
        # a row's page table: wider than its pages by a window group's ring
        self.table_width = self.spec.table_width
        self._window = max(g.window for g in self.spec.page_groups)
        # what knows pages only refuses a recurrent state here, before
        # anything is placed or traced
        self.recurrent = bool(self.spec.recurrent_layers)
        if prefix_cache:
            refuse_recurrent(
                self.spec, "inference.prefix_cache",
                "a shared page says nothing of the state after it "
                "(drop the key: such a model is served with it off)")
        if prefix_cache:
            refuse_window_ring(
                self.spec, "inference.prefix_cache",
                "a shared page of a full layer says nothing of the ring "
                "beside it, which holds the sharer's last window only "
                "(drop the key: such a model is served with it off)")
        self.prefix_cache = \
            (not self.recurrent and not self.spec.ring_pages) \
            if prefix_cache is None else bool(prefix_cache)
        if self.tier is not None:
            refuse_window_ring(
                self.spec, f"the disaggregated {self.tier!r} tier",
                "the hand-off moves a row's pages and no ring")
            refuse_recurrent_or_latent(
                self.spec, f"the disaggregated {self.tier!r} tier",
                "the hand-off moves pages and no state",
                "the hand-off has not been run on a pool without a v "
                "leaf")
        if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
            refuse_recurrent_or_latent(
                self.spec, "a 'model' mesh axis (tensor parallelism)",
                "the state's heads are not sharded",
                "a latent is one head, and every query head reads all "
                "of it")
            refuse_window_ring(
                self.spec, "a 'model' mesh axis (tensor parallelism)",
                "the groups' heads are not sharded")
        # counters the model's decode step returns beside its logits
        # (`models/mla_moe.py`: the expert layers' pairs), by name
        self._counter_names = tuple(getattr(model, "serve_counters", ()))
        # the flash kernel's visit set, for decode()'s counters
        self._paged_grid_blocks = None
        if self.attention_impl == "flash":
            # a block/storage geometry the flash kernel cannot compile
            # for this device is a typed error here, at build time
            from deepspeed_tpu.ops.pallas.flash_decode import (
                check_decode_geometry, paged_grid_blocks)
            self._paged_grid_blocks = paged_grid_blocks
            # the kernel holds all the heads a device has
            tp = dict(mesh.shape).get("model", 1) if mesh is not None else 1
            for group in self.spec.page_groups:
                self.attention_block_k = check_decode_geometry(
                    self.attention_block_k, self.page_size, self.spec.dtype,
                    group.n_head // tp, group.head_dim,
                    self.spec.codec is not None,
                    latent=bool(self.spec.latent_v_dim),
                    v_dim=group.v_dim if self.spec.groups else None)
        self.mesh = mesh
        self.session = session
        self._sample_key = jax.random.PRNGKey(self.sampling_seed)
        self._small_ints = {}           # see _one_int

        self._cache_shardings = None
        self._weights_placed = False    # see _place_weights
        if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            # commit the sampling key (replicated) up front: an
            # uncommitted first-call key would compile the decode
            # program once, come back committed, and recompile on the
            # second step — breaking the 2-program contract under TP.
            self._sample_key = jax.device_put(
                self._sample_key, NamedSharding(mesh, PartitionSpec()))
            self._cache_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec),
                kv_partition_specs(self.spec),
                is_leaf=lambda x: not isinstance(x, dict))
        with Span("pool"):
            self.cache = self._new_pool()

        # cache (arg 1) is donated in both programs: the page pool
        # updates in place instead of doubling HBM every step. Page
        # tables are plain int32 DATA inputs with a fixed shape, so
        # allocator churn never reaches a jit boundary.
        self._prefill = donated_jit(self._prefill_fn, donate_argnums=(1,))
        self._decode = donated_jit(self._decode_fn, donate_argnums=(1,))
        self.params = params        # placed below

        # speculative decoding (inference.speculative block): a draft
        # + verify program pair hung off the engine, or None when the
        # block is absent/disabled/degenerate — in which case the
        # 2-program contract above is unchanged. When present, the
        # contract is 3 programs (prefill, draft, verify) and the
        # plain decode program must stay at 0 jit-cache entries.
        from deepspeed_tpu.inference.speculative import build_speculative
        self.speculative = build_speculative(self, config)
        if self.tier is not None and self.speculative is not None:
            raise ValueError(
                "inference.speculative cannot combine with a tiered "
                "(disaggregated) engine — the draft/verify pair would "
                "break the one-program-per-tier contract")

        # last, once nothing can refuse the model any more: placing
        # traces the decode program
        with Span("params", attrs={}) as placing:
            self._place_weights(placing.attrs)
        self._register_programs()

    # -- compiled programs --------------------------------------------------

    def _register_programs(self):
        """The two programs by name, for whoever joins a profiler trace
        with their scopes (`telemetry/programs.py`). What is registered
        is a copy of this engine that holds shapes where it holds
        arrays and no jitted program, so it neither keeps the
        parameters and the pool on the device nor the engine alive, and
        can still trace ``_prefill_fn`` / ``_decode_fn`` once the engine
        is gone. Nothing is traced or lowered here."""
        twin = copy.copy(self)
        twin.params, twin.cache = programs.shapes((self.params, self.cache))
        twin._prefill = twin._decode = twin.speculative = None
        donated = self._decode._ds_donate_argnums   # the pool, in both
        programs.register("prefill", lambda: (
            twin._prefill_fn, donated, twin.prefill_lowering_args()))
        programs.register("decode", lambda: (
            twin._decode_fn, donated, twin.decode_lowering_args()))

    def _place_weights(self, counters):
        """``self.params`` over the mesh where there is a ``model``
        axis, and every leaf in the format the decode program's
        compiler asks for (:func:`asked_weight_formats`), once, before
        either program has run. The decode program runs every step, so
        it decides; both jits take the formats of the committed arrays
        they are handed, so no program has a layout written into it. A
        leaf that lies right stays the array it is;
        one that does not is copied into its format, leaf by leaf, and
        the engine keeps the copy alone (the tree it was handed is the
        caller's, who may build another engine on it: nothing is
        donated, and the old leaf goes when the caller lets go).
        ``counters`` (the ``setup/engine/params`` span's attributes)
        get ``weights_asked``, ``weights_relaid`` and
        ``weights_relaid_bytes``; where a leaf is not to be asked about
        (:func:`_askable`: no layout named, the host's CPU) nothing is
        asked or placed, and all three read 0."""
        params = self.params
        if self._cache_shardings is not None:
            from jax.sharding import NamedSharding
            params = self.params = jax.tree_util.tree_map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(self.mesh, spec)),
                params, self.model.partition_specs(params))
        counters.update(weights_asked=0, weights_relaid=0,
                        weights_relaid_bytes=0)
        leaves, tree = jax.tree_util.tree_flatten_with_path(params)
        if not all(_askable(leaf) for _, leaf in leaves):
            return
        asked = tree.flatten_up_to(asked_weight_formats(
            self._decode.__wrapped__,
            programs.shapes(self.decode_lowering_args()),
            self._decode._ds_donate_argnums))
        counters["weights_asked"] = len(leaves)
        placed, relaid = [], []
        for (path, leaf), want in zip(leaves, asked):
            if leaf.format.layout != want.layout:
                relaid.append(f"{jax.tree_util.keystr(path)} "
                              f"{leaf.dtype.name}{list(leaf.shape)}")
                counters["weights_relaid_bytes"] += leaf.nbytes
                leaf = jax.device_put(leaf, want)
            placed.append(leaf)
        counters["weights_relaid"] = len(relaid)
        if relaid:
            self.params = tree.unflatten(placed)
            # a placed weight is a committed array, and a program handed
            # one returns committed results: the pool and the sampling
            # key go round through both programs, so they start as they
            # will come back (else the second call of each compiles again)
            self._weights_placed = True
            self._sample_key = _committed(self._sample_key)
            self.cache = jax.tree_util.tree_map(_committed, self.cache)
            logger.info(
                "InferenceEngine: %d of %d weights (%d bytes) placed in the "
                "format the decode program asks for: %s", len(relaid),
                len(leaves), counters["weights_relaid_bytes"],
                ", ".join(relaid))

    def _new_pool(self):
        """A zeroed pool as the programs are handed it: over the mesh
        under a ``model`` axis, committed to its device beside placed
        weights."""
        cache = init_kv_cache(self.spec)
        if self._cache_shardings is not None:
            return jax.tree_util.tree_map(
                jax.device_put, cache, self._cache_shardings)
        if self._weights_placed:
            return jax.tree_util.tree_map(_committed, cache)
        return cache

    def _pin_cache(self, cache):
        """Constrain the output cache to the same shardings the input
        carries: without the pin GSPMD may pick a different output
        layout, and the NEXT call's changed input shardings would cost
        the recompile the whole engine exists to avoid."""
        if self._cache_shardings is None:
            return cache
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, cache,
            self._cache_shardings)

    @property
    def _attn_mesh(self):
        """The TP mesh an attention call is told of: the engine's where
        its ``model`` axis shards the pool, else none."""
        return self.mesh if self._cache_shardings is not None else None

    def _prefill_fn(self, params, cache, tokens, positions, page_table,
                    slots, n_valid):
        # prefill addresses the POOL through the chunk's page table and
        # the row's recurrent leaves (if the model has any) through its
        # slot; the whole cache flows through so donation updates it in
        # place. The logits are the last real token's.
        # Every pool's chunk obeys ``attention_impl`` as a decode step
        # does: a latent pool's (the prefill kernel), a window group's
        # (the band's kernel; a full group's walk takes the dense path
        # whatever it is told) and a per-head pool's (the chunk's
        # kernel, where `cache.chunk_kernel_takes` the call's shapes
        # and dtypes; else, and under a TP mesh, the dense arm).
        logits, cache = self.model.serve_apply(
            params, cache, tokens, positions, page_table, slots,
            n_valid, attn_impl=self.attention_impl,
            attn_mesh=self._attn_mesh)[:2]
        # fp32 on the way out: a reader gets full precision whatever the
        # compute dtype (a no-op for f32 models: parity stays bit-exact).
        # prefill() copies this one row home; decode() copies no logits.
        with jax.named_scope("ds_head"):
            logits = logits.astype(jnp.float32)
        return logits, self._pin_cache(cache)

    def _decode_fn(self, params, cache, tokens, positions, page_tables,
                   key):
        # attention impl / block size / sampling knobs are static (read
        # off self at trace time): they select the traced graph, never
        # ride as runtime values — changing them means a new engine.
        # row i sits in slot i; a row whose table starts with the trash
        # page holds no request (its token is not real)
        live = (page_tables[:, 0] != TRASH_PAGE).astype(jnp.int32)
        logits, cache, *counters = self.model.serve_apply(
            params, cache, tokens[:, None], positions[:, None],
            page_tables, jnp.arange(self.max_batch, dtype=jnp.int32), live,
            attn_impl=self.attention_impl,
            attn_block_k=self.attention_block_k,
            attn_mesh=self._attn_mesh)
        from deepspeed_tpu.inference.sampling import sample_logits
        with jax.named_scope("ds_sample"):
            next_tokens, key = sample_logits(
                logits, key, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p)
            if self._counter_names:
                # behind the tokens, so that they come back in the
                # tokens' own transfer: decode() takes them off again
                next_tokens = jnp.concatenate([next_tokens, jnp.stack([
                    counters[0][name].astype(next_tokens.dtype)
                    for name in self._counter_names])])
            logits = logits.astype(jnp.float32)
        return next_tokens, logits, key, self._pin_cache(cache)

    # -- host API -----------------------------------------------------------

    def prefill(self, slot, prompt, page_table, start=0):
        """Chunked prefill of ``prompt`` (token ids) for batch row
        ``slot``; returns the fp-logits at the last prompt token
        (``[vocab]``, numpy) — what greedy sampling of the first
        generated token reads.

        ``page_table`` (``[pages_per_row]`` ints, pages covering the
        prompt allocated by the scheduler) addresses the pool, ``slot``
        names the row (whose recurrent leaves, in a model that has
        them, the prompt's first chunk overwrites from zero and each
        later chunk carries on), and ``start``
        (chunk-aligned) resumes mid-prompt — a prefix-cache hit skips
        the chunks the shared pages already hold; a parked-session
        resume restarts at the session's frontier. The skipped span's KV is bit-identical by
        construction: prefill is deterministic, so re-running it would
        write the same bytes the shared pages already carry."""
        if self.tier == "decode":
            raise RuntimeError(
                "decode-tier engine: the prefill program is pinned off "
                "— prefill belongs to the prefill tier")
        n = len(prompt)
        if not 0 < n <= self.max_seq:
            raise ValueError(
                f"prompt length {n} outside (0, max_seq={self.max_seq}]")
        # one span for the whole prompt, its uploads included; ``rid``
        # and ``rows_waiting`` (the rows in decode that wait while this
        # prompt is prefilled) are the enclosing (scheduler's ``admit``)
        # span's, so the call takes no new argument
        attrs = {"rid": enclosing_attr("rid"),
                 "rows_waiting": enclosing_attr("rows_waiting")}
        with Span("prefill", self.session, attrs):
            return self._prefill_chunks(slot, prompt, page_table, start,
                                        attrs)

    def _prefill_chunks(self, slot, prompt, page_table, start, attrs):
        n = len(prompt)
        chunk = self.prefill_chunk
        padded = -(-n // chunk) * chunk
        toks = np.zeros((1, padded), np.int32)
        toks[0, :n] = np.asarray(prompt, np.int32)
        last_chunk = (n - 1) // chunk
        pt = jnp.asarray(np.asarray(page_table, np.int32).reshape(1, -1))
        # the last chunk always runs (it produces the logits the first
        # sampled token reads), so a resume start clamps to it.
        start = min(int(start), last_chunk * chunk)
        if start % chunk:
            raise ValueError(
                f"prefill start {start} must be chunk-aligned "
                f"(chunk={chunk})")
        attrs["chunks"] = (padded - start) // chunk
        attrs["pad_tokens"] = padded - n
        if self.spec.latent_v_dim or self.spec.groups:
            # blocks of the walk over the prompt's calls: a latent pool's
            # (and those the prefill kernel took in: all of them or
            # none), or a full group's layers'
            from deepspeed_tpu.inference.cache import (band_kernel_takes,
                                                       latent_walk_block)
            block = latent_walk_block(self.pages_per_row, self.page_size)
            blocks = sum((ci * chunk + chunk - 1) // block + 1
                         for ci in range(start // chunk, padded // chunk))
            if self.spec.latent_v_dim:
                attrs["attn_blocks"] = blocks
                attrs["attn_blocks_kernel"] = \
                    blocks if self.attention_impl == "flash" else 0
            else:
                attrs["attn_prefix_blocks_full"] = blocks
                # the window layers' bands, one a layer a call, and
                # those of them the band's kernel took (all of a group's
                # or none)
                bands = [(attrs["chunks"] * len(g.layers), g.window)
                         for g in self.spec.groups if g.window]
                attrs["attn_window_calls"] = sum(n for n, _ in bands)
                attrs["attn_window_calls_kernel"] = sum(
                    n for n, window in bands
                    if band_kernel_takes(self.attention_impl, window))
        else:
            # the attention layers' chunks over a per-head pool, one a
            # layer a call, and those of them the chunk's kernel took
            # (all of a model's or none, by the call's shapes)
            calls = attrs["chunks"] * self.spec.n_layer
            attrs["attn_plain_calls"] = calls
            attrs["attn_plain_calls_kernel"] = \
                calls if self._chunk_kernel_takes() else 0
        ssm_state = [shape for leaf, shape, _ in self.spec.recurrent_leaves
                     if leaf == "ssm"]
        if ssm_state:
            # the mixers' chunked scans, one a mixer a call, and those of
            # them the scan's kernel took (`ops/ssm.py`: all of a model's
            # or none, by the call's shapes)
            from deepspeed_tpu.ops.ssm import ssd_kernel_takes
            cfg = self.model.config
            calls = attrs["chunks"] * len(self.spec.recurrent_layers)
            attrs["ssd_scan_calls"] = calls
            attrs["ssd_scan_calls_kernel"] = calls if ssd_kernel_takes(
                chunk, *ssm_state[0][1:], cfg.mamba_n_groups,
                cfg.mamba_chunk_size, cfg.dtype) else 0
        if start:
            refuse_recurrent(
                self.spec, f"a prefill resumed at token {start}",
                "the state before it was never computed")
        slots = self._one_int(int(slot))
        from deepspeed_tpu.runtime.resilience import fault_injection
        last = None
        for ci in range(start // chunk, padded // chunk):
            # disagg soak seam: an armed prefill_chunk kill dies HERE,
            # mid-prompt, with pages allocated and partially written.
            fault_injection.maybe_kill("prefill_chunk", ci)
            tc = jnp.asarray(toks[:, ci * chunk:(ci + 1) * chunk])
            pc = jnp.arange(ci * chunk, (ci + 1) * chunk,
                            dtype=jnp.int32)[None, :]
            logits, self.cache = self._prefill(
                self.params, self.cache, tc, pc, pt, slots,
                self._one_int(min(chunk, n - ci * chunk)))
            if ci == last_chunk:
                last = np.asarray(logits[0])
        return last

    def _chunk_kernel_takes(self):
        """`cache.chunk_kernel_takes` of this engine's prefill call, as
        the program asks it of each attention layer."""
        from deepspeed_tpu.inference.cache import chunk_kernel_takes
        cfg = getattr(self.model, "config", None)
        q_heads = getattr(cfg, "num_attention_heads", None) or \
            getattr(cfg, "n_head", None)
        if not q_heads:
            return False
        return chunk_kernel_takes(
            self.attention_impl, 1, self.prefill_chunk,
            q_heads // self.spec.n_head, self.spec.head_dim, cfg.dtype,
            self.spec.dtype, self.spec.codec is not None, self._attn_mesh)

    def _one_int(self, value):
        """``[value]`` as a device array, uploaded once a value: a
        prefill's slot and each chunk's count of real tokens are the
        same few numbers call after call."""
        arr = self._small_ints.get(value)
        if arr is None:
            arr = self._small_ints[value] = jnp.asarray(
                np.asarray([value], np.int32))
        return arr

    def decode(self, tokens, positions, page_tables):
        """One decode step for every cache row at once. ``tokens`` /
        ``positions``: ``[max_batch]`` int arrays (inactive rows padded
        with zeros — their outputs are meaningless and ignored).
        Returns ``(next_tokens, logits)``: the tokens ``[max_batch]`` as
        numpy, the float32 logits ``[max_batch, vocab]`` as the
        ``jax.Array`` the program produced, still on the device. A
        caller that reads them pays for the copy (``np.asarray``, or an
        index); the scheduler reads none. Sampling (greedy argmax, or
        temperature/top-k/top-p with the threaded PRNG key) happens
        in-program so it costs no extra device round trip.
        ``page_tables``: ``[max_batch,
        pages_per_row]`` (inactive rows all zeros — their garbage token
        lands on the trash page)."""
        if self.tier == "prefill":
            raise RuntimeError(
                "prefill-tier engine: the decode program is pinned off "
                "— decode belongs to the decode tier")
        session = self.session
        attrs = None
        if self._counter_names:
            attrs = {}
        if self._paged_grid_blocks is not None or self.recurrent:
            # the rows that hold a request
            rows = int(np.count_nonzero(
                np.asarray(page_tables)[:, 0] != TRASH_PAGE))
        if self._paged_grid_blocks is not None:
            # how far the kernel's grid follows the cache: KV blocks (of
            # all heads) the live rows hold against those it visits (of
            # a full group's layers; a window group's beside them below)
            live, launched = self._paged_grid_blocks(
                positions, page_tables, self.attention_block_k)
            # and its write: the rows whose block it writes back are the
            # rows that hold a request (the loop it replaced wrote every
            # row of the batch, whatever it held)
            attrs = dict(attrs or {}, kv_blocks_live=live,
                         kv_blocks_launched=launched,
                         kv_rows_live=rows, kv_rows_written=rows)
        window = self._window
        if self._paged_grid_blocks is not None and window:
            # a window layer's walk: the blocks the kernel visits
            # against those the rows' windows reach
            seen, walked = self._paged_grid_blocks(
                positions, page_tables, self.attention_block_k, window)
            attrs.update(attn_blocks_in_window=seen,
                         attn_blocks_visited_window=walked)
        if self.recurrent:
            # rows whose state the step moves on, against those whose
            # state it reads and writes back (all of them: the update is
            # one masked pass over every slot)
            attrs = dict(attrs or {}, ssm_rows_live=rows,
                         ssm_rows_touched=self.max_batch)
        # three spans, so that a gap on the device can be laid to the
        # part of the call the host was in: the uploads, the dispatch,
        # the wait for the tokens (the device's own time). The logits
        # stay where the program left them
        with Span("decode", session, attrs):
            with Span("upload", session):
                args = [jnp.asarray(np.asarray(tokens, np.int32)),
                        jnp.asarray(np.asarray(positions, np.int32)),
                        jnp.asarray(np.asarray(page_tables, np.int32))]
            with Span("dispatch", session):
                nxt, logits, self._sample_key, self.cache = self._decode(
                    self.params, self.cache, *args, self._sample_key)
            with Span("wait_tokens", session):
                nxt = np.asarray(nxt)
                if self._counter_names:
                    attrs.update(zip(self._counter_names,
                                     nxt[self.max_batch:].tolist()))
                    nxt = nxt[:self.max_batch]
        return nxt, logits

    # -- host-RAM page tier -------------------------------------------------

    def _refuse_page_moves(self, feature):
        refuse_recurrent(self.spec, feature,
                         "pages are copied and no state with them")
        refuse_window_ring(self.spec, feature,
                           "pages are copied and no ring with them")

    def gather_pages(self, page_ids):
        """Snapshot the given physical pages to host RAM: a per-layer
        ``{"k": [n, H, D, page_size], ...}`` numpy pytree, copied with
        the hot-checkpoint snapshot-isolation discipline
        (`runtime/resilience/hotckpt.py:_snapshot_to_host` — the
        compiled steps donate the pool, so host views must never alias
        live device memory). Runs OUTSIDE the two compiled programs:
        parking is host-side admission work, the steady-state decode
        program stays transfer-free."""
        from deepspeed_tpu.runtime.resilience.hotckpt import (
            _snapshot_to_host)
        self._refuse_page_moves("gather_pages (park / hand-off)")
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        axis = 1 if self.spec.stacked else 0
        gathered = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, ids, axis=axis), self.cache)
        return _snapshot_to_host(gathered)

    def gather_pages_device(self, page_ids):
        """Like :meth:`gather_pages` but the snapshot STAYS on device:
        a pytree of fresh (immutable) device arrays, never a host round
        trip. This is the in-process disaggregated handoff's source
        half — the decode tier scatters these arrays straight into its
        own pool (:meth:`scatter_pages` accepts device values), so the
        prefill→decode page copy is device-to-device and keyed purely
        by page ids. The copies are materialized eagerly so they can't
        alias pool buffers a later donated prefill call invalidates."""
        self._refuse_page_moves("gather_pages_device (hand-off)")
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        axis = 1 if self.spec.stacked else 0
        gathered = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, ids, axis=axis), self.cache)
        jax.block_until_ready(gathered)
        return gathered

    def scatter_pages(self, page_ids, host_pages):
        """Inverse of :meth:`gather_pages`: write a host page snapshot
        back into (freshly allocated) physical pages — the resume half
        of the host tier."""
        self._refuse_page_moves("scatter_pages (resume / hand-off)")
        ids = np.asarray(page_ids, np.int32)
        axis = 1 if self.spec.stacked else 0

        def upd(leaf, vals):
            vals = jnp.asarray(vals, leaf.dtype)
            if axis == 0:
                return leaf.at[ids].set(vals)
            return leaf.at[:, ids].set(vals)

        self.cache = jax.tree_util.tree_map(upd, self.cache, host_pages)
        if self._cache_shardings is not None:
            # eager .at updates drop the committed sharding; re-place
            # so the next compiled call sees the pinned layout.
            self.cache = jax.tree_util.tree_map(
                jax.device_put, self.cache, self._cache_shardings)

    def sample_first(self, last_logits):
        """Sample the FIRST generated token from prefill's last-prompt-
        token logits (``[vocab]`` numpy) with the same temperature /
        top-k / top-p pipeline the compiled decode step uses — one tiny
        eager call at admission time, sharing the decode key stream so
        a fixed request stream samples reproducibly."""
        from deepspeed_tpu.inference.sampling import sample_logits
        tok, self._sample_key = sample_logits(
            jnp.asarray(last_logits, jnp.float32), self._sample_key,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        return int(tok)

    def reset(self):
        """Zero the cache (rows all free). Compiled programs survive —
        a reset must not cost a recompile."""
        self.cache = self._new_pool()

    # -- recompile detector + audit surface ---------------------------------

    def compile_counts(self):
        """Jit-cache entry counts ``{"prefill": n, "decode": n}`` — the
        serving analog of `analysis/audit.py:compiled_cache_size`. 1/1
        after warmup and FOREVER after is the contract; growth means a
        shape or dtype leaked into a compiled boundary."""
        progs = [("prefill", self._prefill), ("decode", self._decode)]
        if self.speculative is not None:
            progs += [("draft", self.speculative._draft),
                      ("verify", self.speculative._verify)]
        out = {}
        for name, fn in progs:
            cs = getattr(fn, "_cache_size", None)
            try:
                out[name] = int(cs()) if callable(cs) else None
            except Exception:
                out[name] = None
        return out

    def recompile_findings(self, baseline=1):
        """In-engine recompile detector: error Findings when either
        compiled program's cache outgrew ``baseline`` entries."""
        from deepspeed_tpu.analysis.rules import SEV_ERROR, Finding
        findings = []
        for name, n in self.compile_counts().items():
            if n is not None and n > baseline:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"{name} program has {n} jit cache entries "
                    f"(expected {baseline}) — the serving loop "
                    f"recompiled mid-stream",
                    {"program": name, "cache_size": n,
                     "expected": baseline}))
        return findings

    def decode_lowering_args(self):
        """The exact avals :meth:`decode` calls with — lowering through
        these is a jit-cache hit, never a fresh compile."""
        return (self.params, self.cache,
                jnp.zeros((self.max_batch,), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32),
                jnp.zeros((self.max_batch, self.table_width), jnp.int32),
                self._sample_key)

    def prefill_lowering_args(self):
        """The avals one prefill chunk is called with."""
        one = jnp.zeros((1,), jnp.int32)
        return (self.params, self.cache,
                jnp.zeros((1, self.prefill_chunk), jnp.int32),
                jnp.zeros((1, self.prefill_chunk), jnp.int32),
                jnp.zeros((1, self.table_width), jnp.int32), one, one)

    def decode_hlo(self):
        """Compiled HLO text of the decode program (audit/bench food)."""
        args = self.decode_lowering_args()
        return self._decode.lower(*args).compile().as_text()

    def cache_facts(self):
        """Static cache facts for audits and the bench row."""
        facts = {"bytes": kv_cache_nbytes(self.cache),
                 "dtype_census": cache_dtype_census(self.cache),
                 "kv_cache_dtype": self.kv_cache_dtype,
                 "max_batch": self.max_batch,
                 "max_seq": self.max_seq,
                 "seq_buckets": list(self.seq_buckets),
                 "prefill_chunk": self.prefill_chunk,
                 "stacked": self.spec.stacked,
                 "page_size": self.page_size,
                 "n_pages": self.n_pages,
                 "pages_per_row": self.pages_per_row}
        if self.spec.groups:
            facts["table_width"] = self.table_width
            facts["groups"] = {
                g.name: {"layers": len(g.layers), "n_head": g.n_head,
                         "head_dim": g.head_dim, "v_dim": g.v_dim,
                         "window": g.window, "n_pages": g.n_pages}
                for g in self.spec.groups}
        if self.recurrent:
            facts["recurrent_layers"] = len(self.spec.recurrent_layers)
            facts["state_bytes_per_slot"] = self.spec.state_bytes_per_slot
        if self.tier is not None:
            facts["tier"] = self.tier
        if self.speculative is not None:
            facts["speculative"] = self.speculative.facts()
        return facts
