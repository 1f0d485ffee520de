"""The closed vocabulary of the program's device-side scopes.

Every ``jax.named_scope`` (and every Pallas kernel's ``name``) of the
tree starts with ``ds_`` and is listed here with the layer of
``PERF.md`` section 3 it belongs to and one line on what it covers.
Data only: nothing here is imported on a hot path. A scope lives in the
locations of the lowered program, which ``lower().as_text()`` does not
print, and reaches the compiled program as the ``op_name`` of each
instruction's metadata; `telemetry/programs.py` hands that map out.

How a reader lays an instruction to a scope:

- an instruction belongs to the **innermost** vocabulary scope of its
  ``op_name`` (:func:`innermost`); a sum over a scope takes its children
  (:func:`chain`), as a substring match on the ``op_name`` does;
- a fusion carries the ``op_name`` of its root, so a fusion is its
  root's: a matmul fused under a residual add is the add's scope's;
- the backward of a scope reads ``transpose(jvp(ds_x))``: the same name.

`tests/unit/test_scope_vocabulary.py` holds every ``ds_*`` literal of
the tree to this table, the table to `docs/observability.md`, and every
name to the rule that none contains, or is contained in, a scope string
an accepted benchmark metric sums.
"""

import re

# name -> (layer of PERF.md section 3, what it covers)
SCOPES = {
    # --- what every model has ------------------------------------------
    "ds_embed": ("serving loop", "token and position lookup, the "
                 "embedding multiplier, the rotary angles and the mask "
                 "of real tokens that every layer reads"),
    "ds_attn_qkv": ("kernels", "an attention's input norm and input "
                    "projections with their QK-norm and rotary"),
    "ds_attn_out": ("kernels", "an attention's output projection and "
                    "the residual add behind it"),
    "ds_attn_train": ("kernels", "attention proper of a forward without "
                      "a cache (training): the flash kernels' calls, or "
                      "the dense scores"),
    "ds_attn_decode_plain": ("kernels", "cached_attention outside page "
                             "groups and latents, one token a row: the "
                             "decode kernel's call or the dense oracle"),
    "ds_attn_prefill_plain": ("kernels", "cached_attention outside page "
                              "groups and latents, a prompt's chunk: the "
                              "page write and the chunk's kernel, or the "
                              "bucket-long read and the dense scores"),
    "ds_kv_write": ("KV cache", "paged_write_kv: a chunk's (or, under "
                    "the dense oracle, a token's) keys and values into "
                    "their pages; inside the attention's scope"),
    "ds_mlp": ("serving loop", "a dense feed-forward block with its "
               "norm and residual add"),
    "ds_experts": ("experts", "an expert layer's norm, what joins its "
                   "routed and shared parts and its residual add; its "
                   "ds_moe_* phases lie inside"),
    "ds_head": ("serving loop", "the final norm, the pick of each row's "
                "last real token and the logits' product"),
    "ds_sample": ("serving loop", "sampling in engine._decode_fn, and "
                  "the expert layers' counters brought together and laid "
                  "behind the tokens"),
    # --- training step --------------------------------------------------
    "ds_loss": ("training step", "the cross entropy (chunked with the "
                "head where the loss applies it itself) and the router "
                "losses"),
    "ds_param_cast": ("training step", "the parameters' 16-bit copy and, "
                      "under ZeRO 1 / 2, its gather"),
    "ds_grad_epilogue": ("training step", "the gradients' norm, clip and "
                         "overflow vote, the loss scale's update and the "
                         "step's metrics"),
    "ds_opt_update": ("training step", "the optimizer's update and the "
                      "select that an overflowed step keeps the old "
                      "state by"),
    # --- experts (moe/dropless.py and the models' expert layers) --------
    "ds_moe_route": ("experts", "router logits, top-k, the pairs' order"),
    "ds_moe_dispatch": ("experts", "rows gathered into expert order"),
    "ds_moe_experts": ("experts", "the grouped matmuls"),
    "ds_moe_combine": ("experts", "the weighted sum back to token order"),
    "ds_moe_shared": ("experts", "the shared expert"),
    "ds_moe_latent_down": ("experts", "tokens into the experts' latent"),
    "ds_moe_latent_up": ("experts", "the experts' latent back to hidden"),
    "ds_moe_unwritten_rows": ("experts", "kernel: zeroes the rows of the "
                              "grouped matmul's output no tile wrote"),
    # --- recurrent state ------------------------------------------------
    "ds_ssm_mixer": ("recurrent state", "a Mamba-2 mixer whole, with its "
                     "norm and residual add; the five ds_ssm_* below lie "
                     "inside"),
    "ds_ssm_in_proj": ("recurrent state", "the mixer's input projection"),
    "ds_ssm_conv": ("recurrent state", "the causal convolution and its "
                    "window"),
    "ds_ssm_scan": ("recurrent state", "the state's recurrence: a chunked "
                    "scan or one step"),
    "ds_ssm_gate_norm": ("recurrent state", "the gate and grouped norm"),
    "ds_ssm_out_proj": ("recurrent state", "the mixer's output projection"),
    "ds_ssd_prefill": ("recurrent state", "ops/ssm.py: the chunked scan"),
    "ds_ssm_decode": ("recurrent state", "ops/ssm.py: the one-step update"),
    "ds_gdn_mixer": ("recurrent state", "a Gated DeltaNet mixer whole, "
                     "with its norm, projections, gate and residual add; "
                     "the three ds_gdn_* below lie inside"),
    "ds_gdn_conv": ("recurrent state", "the delta rule's convolution"),
    "ds_gdn_scan": ("recurrent state", "the delta rule over a chunk"),
    "ds_gdn_step": ("recurrent state", "the delta rule's decode step"),
    "ds_gated_delta_chunked": ("recurrent state", "kernel: the chunked "
                               "delta rule"),
    "ds_gdn_step_rows": ("recurrent state", "kernel: the decode step "
                         "over the live rows"),
    "ds_kda_mixer": ("recurrent state", "a Kimi Delta Attention mixer "
                     "whole, with its norm, projections, convolutions, "
                     "output gate and residual add; the ds_kda_* below "
                     "lie inside"),
    "ds_kda_gate": ("recurrent state", "the mixer's two full-rank gate "
                    "projections and beta's, the bounded decay g a "
                    "channel, beta and the output gate's sigmoid"),
    "ds_kda_scan": ("recurrent state", "the per-channel delta rule over "
                    "a chunk"),
    "ds_kda_step": ("recurrent state", "the per-channel delta rule's "
                    "decode step"),
    "ds_kda_scan_chunks": ("recurrent state", "kernel: the chunked "
                           "per-channel delta rule"),
    "ds_kda_step_rows": ("recurrent state", "kernel: the per-channel "
                         "decode step over the live rows"),
    "ds_sconv_mixer": ("recurrent state", "a short-convolution mixer "
                       "whole, with its norm and residual add; the three "
                       "ds_sconv_* below lie inside"),
    "ds_sconv_in_proj": ("recurrent state", "the mixer's input "
                         "projection to b, c and x"),
    "ds_sconv_taps": ("recurrent state", "the two gates, the taps, and "
                      "the window's read and write: what is neither "
                      "projection"),
    "ds_sconv_out_proj": ("recurrent state", "the mixer's output "
                          "projection"),
    # --- attention of the grouped, latent and gated kinds ----------------
    "ds_attn_qk_norm": ("kernels", "the RMS norm a head of queries and "
                        "keys; inside ds_attn_qkv"),
    "ds_mla_project": ("kernels", "latent attention's down- and "
                       "up-projections, rotary, absorption and output "
                       "projection"),
    "ds_mla_prefill_attn": ("kernels", "a latent pool's chunk: its write "
                            "and its walk over the row's live blocks"),
    "ds_mla_decode_attn": ("kernels", "a latent pool's decode step"),
    "ds_attn_gate": ("kernels", "an attention's output gate"),
    "ds_attn_decode_full": ("kernels", "a full page group's decode step"),
    "ds_attn_decode_window": ("kernels", "a window group's decode step"),
    "ds_attn_prefill_full": ("kernels", "a full page group's chunk: its "
                             "write and its walk"),
    "ds_attn_prefill_window": ("kernels", "a window group's chunk: the "
                               "band over the ring, then the write"),
    # --- kernels ----------------------------------------------------------
    "ds_flash_fwd": ("kernels", "kernel: training flash forward"),
    "ds_flash_dq": ("kernels", "kernel: training flash dQ"),
    "ds_flash_dkv": ("kernels", "kernel: training flash dK / dV"),
    "ds_flash_decode_paged": ("kernels", "kernel: decode over pages"),
    "ds_flash_prefill_latent": ("kernels", "kernel: a latent block into "
                                "the running softmax"),
    "ds_flash_prefill_paged": ("kernels", "kernel: a prompt's chunk over a "
                               "pool of per-head pages"),
    "ds_window_prefill_band": ("kernels", "kernel: a window layer's band"),
}

TOKEN = re.compile(r"ds_[a-z0-9_]+")


def chain(op_name):
    """The vocabulary scopes of an instruction's ``op_name``, outermost
    first (a name twice where two nested scopes share it)."""
    return [t for t in TOKEN.findall(op_name or "") if t in SCOPES]


def innermost(op_name):
    """The scope an instruction belongs to; ``None`` under none."""
    found = chain(op_name)
    return found[-1] if found else None
