"""Plain reference for the OLMoE configurations: the published forward
pass, the three terms of the training loss and (through ``jax.grad`` of
``loss``) their gradients, in straightforward ``jax.numpy`` and float32.
No kernel, no sort, no gather by expert, no engine: every expert is
applied to every token and the result is masked by the top-k choice, so
nothing here is shared with the program's dispatch. Matrix products run
at ``highest`` precision (on a TPU a float32 product is otherwise done
in bf16 passes).

It follows ``transformers/models/olmoe/modeling_olmoe.py`` (Muennighoff
et al. 2024, arXiv:2409.02060). A layer, with ``n = RMSNorm(x)``
(float32 statistics, learned weight, ``rms_norm_eps``):

- attention: ``q = RMSNorm_q(W_q n)``, ``k = RMSNorm_k(W_k n)``, each a
  norm over the whole hidden-wide vector before the split into heads;
  ``v = W_v n``; rotary embedding on q and k (``rope_theta``, the
  rotate-half convention, positions from 0); causal
  ``softmax(q k^T / sqrt(head size)) v``; ``W_o``; ``h = x + attention``.
- experts, with ``n = RMSNorm(h)``: ``p = softmax(W_r n)`` over all
  experts; the ``num_experts_per_tok`` largest ``p_e`` as they are
  (``norm_topk_prob`` false: not renormalised);
  ``y = sum_e p_e W_down,e (silu(W_gate,e n) * W_up,e n)``;
  ``out = h + y``. No token is dropped: there is no capacity.
- model: embedding, the layers, a final RMSNorm, an untied head.

The loss is ``ce + lb_coef * lb + z_coef * z``:

- ``ce``: mean next-token cross entropy over all but the last position;
- ``lb``: ``load_balancing_loss_func`` of that file: the router
  probabilities of all layers and tokens laid end to end, ``f_e`` = the
  token-slots sent to expert e over the number of tokens (so the ``f_e``
  sum to ``num_experts_per_tok``, and ``lb`` is ``num_experts_per_tok``
  for a uniform router), ``P_e`` = its mean probability,
  ``lb = num_experts * sum_e f_e P_e``;
- ``z``: the router z-loss of the OLMoE paper (section 3, from ST-MoE):
  the mean over layers and tokens of ``logsumexp(router logits)^2``.

Departures from the published code, each noted in the configuration
file too: (1) the two coefficients are not keys of the model's
``config.json``; they are the configuration file's ``assumed``
``router_aux_loss_coef`` (0.01, that file's default) and
``router_z_loss_coef`` (0.001, the paper's), passed in by the caller.
(2) The published code computes the router's product in the model's
type (bf16 when trained so) and only the softmax in float32; here, as
everything else, it is float32. (3) No attention mask and no padding:
sequences are full. (4) ``clip_qkv`` is null in the configuration and
not implemented.

``params`` is the program's parameter tree (``embed``,
``layers_<i>/{input_norm, attn/{q_proj, k_proj, v_proj, o_proj, q_norm,
k_norm}, post_attn_norm, experts/{router, w_gate, w_up, w_down}}``,
``final_norm``, ``lm_head``; a norm holds its ``weight``; a product is
``x @ W`` with ``W`` stored ``[in, out]``, the banks ``[experts, in,
out]``), read in float32 whatever type it is stored in.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * \
        _f32(p["weight"])


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """``x`` ``[B, T, H, D]``, positions 0..T-1."""
    T, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _attention(n, p, n_head, eps, theta):
    B, T, C = n.shape
    q = _rms_norm(_mm(n, p["q_proj"]), p["q_norm"], eps)
    k = _rms_norm(_mm(n, p["k_proj"]), p["k_norm"], eps)
    v = _mm(n, p["v_proj"])
    q, k, v = (a.reshape(B, T, n_head, C // n_head) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    att = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / \
        jnp.sqrt(jnp.float32(C // n_head))
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    y = jnp.einsum("bhts,bshd->bthd", att, v, precision=HIGHEST)
    return _mm(y.reshape(B, T, C), p["o_proj"])


def _top_k_mask(probs, top_k):
    """``[N, E]`` of 0/1: the ``top_k`` largest of each row, found one
    ``argmax`` at a time (the lowest index wins a tie)."""
    mask = jnp.zeros_like(probs)
    left = probs
    for _ in range(top_k):
        hit = jax.nn.one_hot(jnp.argmax(left, axis=-1), probs.shape[-1],
                             dtype=probs.dtype)
        mask = mask + hit
        left = jnp.where(hit > 0, -jnp.inf, left)
    return mask


def _experts(n, p, top_k):
    """Every expert on every token, one expert after the other, each
    result weighted by the token's probability of that expert where it
    is among the token's ``top_k`` and by 0 elsewhere. ``n`` ``[N, C]``.
    Returns (y, router logits, mask)."""
    logits = _mm(n, p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    mask = _top_k_mask(probs, top_k)
    weight = probs * mask

    def one(y, bank):
        w_gate, w_up, w_down, w_e = bank
        h = jax.nn.silu(_mm(n, w_gate)) * _mm(n, w_up)
        return y + w_e[:, None] * _mm(h, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        _f32(p["w_gate"]), _f32(p["w_up"]), _f32(p["w_down"]), weight.T))
    return y, logits, mask


def experts(n, p, top_k):
    """One layer's feed-forward part on router input ``n`` ``[N, C]``
    (any dtype, read in float32) with that layer's ``experts``
    parameters ``p``: (``y`` ``[N, C]`` before the residual, router
    probabilities ``[N, E]``, top-k mask ``[N, E]``), float32. For
    holding the program's layer to its own input."""
    with jax.default_matmul_precision("highest"):
        y, logits, mask = _experts(_f32(n), p, top_k)
        return y, jax.nn.softmax(logits, axis=-1), mask


def forward(params, input_ids, cfg):
    """``cfg``: a configuration file's dict. Returns (logits ``[B, T,
    vocab]``, router logits ``[layers, B*T, experts]``, the top-k mask
    of the same shape), all float32."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n_head, top_k = cfg["num_attention_heads"], cfg["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[input_ids]
        B, T, C = x.shape
        n_layer = sum(1 for k in params if str(k).startswith("layers_"))
        router_logits, masks = [], []
        for i in range(n_layer):
            p = params[f"layers_{i}"]
            x = x + _attention(_rms_norm(x, p["input_norm"], eps),
                               p["attn"], n_head, eps, theta)
            n = _rms_norm(x, p["post_attn_norm"], eps)
            y, lg, mask = _experts(n.reshape(B * T, C), p["experts"], top_k)
            x = x + y.reshape(B, T, C)
            router_logits.append(lg)
            masks.append(mask)
        x = _rms_norm(x, params["final_norm"], eps)
        return (_mm(x, params["lm_head"]), jnp.stack(router_logits),
                jnp.stack(masks))


def loss_terms(params, input_ids, cfg, lb_coef, z_coef):
    """``{"loss", "ce", "lb", "z", "logits", "mask"}``: the total, its
    three terms (before their coefficients), the logits and the experts
    chosen (``forward``'s)."""
    lg, router_logits, mask = forward(params, input_ids, cfg)
    logp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
    ce = -jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1).mean()
    E = router_logits.shape[-1]
    flat = router_logits.reshape(-1, E)
    f = mask.reshape(-1, E).mean(0)
    P = jax.nn.softmax(flat, axis=-1).mean(0)
    lb = E * jnp.sum(f * P)
    z = (jax.nn.logsumexp(flat, axis=-1) ** 2).mean()
    return {"loss": ce + lb_coef * lb + z_coef * z, "ce": ce, "lb": lb,
            "z": z, "logits": lg, "mask": mask}


def loss(params, input_ids, cfg, lb_coef, z_coef):
    return loss_terms(params, input_ids, cfg, lb_coef, z_coef)["loss"]
