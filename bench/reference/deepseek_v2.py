"""Plain float32 reference of the DeepSeek-V2-Lite block as the program runs
it (the departures are listed in the configuration file): forward pass,
training loss, gradients, AdamW, and served-token logits.

Straight jax.numpy, float32, no kernels, caches or batching tricks.  It
imports nothing of the program: it reads sizes from the configuration file
and its weights from ``bench.weights``.  Run it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise rounded to bfloat16.

``quant="int4"`` is the control: every operand of the FFN and expert GEMMs
(activations per row, weights per output column) rounded to 4-bit integers,
one step below the e4m3 that the configuration states for those GEMMs.
Two faults that touch the routed experts alone show that a comparison sees
them: ``"int4_experts"`` rounds only the routed experts' operands so, and
``"no_experts"`` drops the routed experts' output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUANTS = (None, "int4", "int4_experts", "no_experts")


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------
def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def rope(x, pos, theta):
    """x (B, S, H, hd); pairs (i, i + hd/2) rotate by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def fake_int4(x, axis):
    """Round to 15 levels of the absmax along ``axis``; gradient passes
    straight through."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    step = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = jnp.clip(jnp.round(x / step), -7, 7) * step
    return x + jax.lax.stop_gradient(q - x)


def swiglu_ffn(x, w13, w2, quant):
    """x (..., D); w13 (D, 2, F); w2 (F, D); ``quant`` True rounds the
    operands to int4."""
    if quant:
        x, w13, w2 = fake_int4(x, -1), fake_int4(w13, 0), fake_int4(w2, 0)
    gate = x @ w13[:, 0]
    up = x @ w13[:, 1]
    a = jax.nn.silu(gate) * up
    if quant:
        a = fake_int4(a, -1)
    return a @ w2


def attention(p, x, pos, n_heads, theta, eps, q_block):
    """Causal multi-head attention, queries in blocks (scores of one block
    at a time are materialized, and recomputed in the backward)."""
    B, S, D = x.shape
    hd = p["wq"].shape[-1] // n_heads
    h = rmsnorm(x, p["ln1_s"], eps)
    q = rope((h @ p["wq"]).reshape(B, S, n_heads, hd), pos, theta)
    k = rope((h @ p["wk"]).reshape(B, S, -1, hd), pos, theta)
    v = (h @ p["wv"]).reshape(B, S, -1, hd)
    rep = n_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    qb = min(q_block, S)
    nb = S // qb

    @jax.checkpoint
    def block(args):
        qi, qpos = args                                  # (B, qb, H, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(hd)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    qs = jnp.moveaxis(q.reshape(B, nb, qb, n_heads, hd), 1, 0)
    o = jax.lax.map(block, (qs, pos.reshape(nb, qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, n_heads * hd)
    return x + o @ p["wo"]


def moe(p, x, top_k, capacity, quant):
    """Routed experts with per-expert capacity (assignments past it are
    dropped in token order, top-k rank order within a token) plus the shared
    experts.  x (T, D).  Returns (y, aux loss, dropped assignments)."""
    T, D = x.shape
    E = p["w_router"].shape[-1]
    logits = x @ p["w_router"]
    probs = jax.nn.softmax(logits, -1)
    gate, ids = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    me = jnp.mean(probs, 0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E), 0)
    aux = E * jnp.sum(me * ce) \
        + 1e-3 * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)

    flat = ids.reshape(-1)                               # (T*k,)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), flat[:, None], 1)[:, 0] - 1
    keep = rank < capacity
    slot = jnp.where(keep, flat * capacity + rank, E * capacity)
    tok = jnp.full((E * capacity + 1,), T, jnp.int32).at[slot].set(
        jnp.arange(T * top_k, dtype=jnp.int32) // top_k)[:-1]
    g = jnp.zeros((E * capacity + 1,), jnp.float32).at[slot].set(
        gate.reshape(-1))[:-1]
    xg = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[tok]
    xg = xg.reshape(E, capacity, D)
    w13, w2 = p["we13"], p["we2"]
    q4 = quant in ("int4", "int4_experts")
    if q4:
        xg, w13, w2 = fake_int4(xg, -1), fake_int4(w13, 1), fake_int4(w2, 1)
    hg = jnp.einsum("ecd,edf->ecf", xg, w13[:, :, 0])
    hu = jnp.einsum("ecd,edf->ecf", xg, w13[:, :, 1])
    a = jax.nn.silu(hg) * hu
    if q4:
        a = fake_int4(a, -1)
    y = jnp.einsum("ecf,efd->ecd", a, w2).reshape(E * capacity, D)
    y = jnp.zeros((T + 1, D), jnp.float32).at[tok].add(y * g[:, None])[:T]
    if quant == "no_experts":
        y = jnp.zeros_like(y)
    y = y + swiglu_ffn(x, p["ws13"], p["ws2"], quant == "int4")
    return y, aux, T * top_k - jnp.sum(keep)


def capacity_of(cfg: dict, tokens: int) -> int:
    """Expert capacity as the program sizes it on one chip."""
    k, E = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    m = cfg["expert_capacity_multiple"]
    c = max(int(tokens * k * cfg["arch"]["capacity_factor"]), 8)
    c = (c + 7) // 8 * 8
    return (max(c // E, 8) + m - 1) // m * m


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def forward(cfg: dict, params, tokens, *, capacity=None, quant=None,
            q_block=512, remat=False):
    """tokens (B, S) -> (logits (B, S, V) f32, aux, dropped).  ``capacity``
    None sizes it as the program does for B*S tokens; ``quant`` is one of
    ``QUANTS``."""
    if quant not in QUANTS:
        raise ValueError(f"unknown quant {quant!r}")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa
    B, S = tokens.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    theta, k = float(cfg["rope_theta"]), cfg["num_experts_per_tok"]
    cap = capacity_of(cfg, B * S) if capacity is None else capacity
    pos = jnp.arange(S, dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def dense_layer(p, x):
        p = f32(p)
        x = attention(p, x, pos, H, theta, eps, q_block)
        h = rmsnorm(x, p["ln2_s"], eps)
        return x + swiglu_ffn(h, p["w13"], p["w2"], quant == "int4")

    def moe_layer(p, x):
        p = f32(p)
        x = attention(p, x, pos, H, theta, eps, q_block)
        h = rmsnorm(x, p["ln2_s"], eps).reshape(B * S, -1)
        y, aux, dropped = moe(p, h, k, cap, quant)
        return x + y.reshape(x.shape), aux, dropped

    if remat:
        dense_layer, moe_layer = jax.checkpoint(dense_layer), \
            jax.checkpoint(moe_layer)
    aux, dropped = jnp.float32(0.0), jnp.int32(0)
    for stack, fn in (("dense_layers", dense_layer), ("layers", moe_layer)):
        if stack not in params:
            continue
        for l in range(params[stack]["wq"].shape[0]):
            p = jax.tree.map(lambda a, _l=l: a[_l], params[stack])
            if stack == "layers":
                x, a, d = fn(p, x)
                aux, dropped = aux + a, dropped + d
            else:
                x = fn(p, x)
    x = rmsnorm(x, params["final_norm_s"].astype(jnp.float32), eps)
    return x @ params["lm_head"].astype(jnp.float32), aux, dropped


def loss(cfg: dict, params, batch, quant=None):
    """Mean next-token cross-entropy over the mask plus the weighted router
    loss; returns (loss, assignments dropped at capacity)."""
    logits, aux, dropped = forward(cfg, params, batch["tokens"], quant=quant,
                                   remat=True)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    m = batch["mask"]
    xent = jnp.sum((lse - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)
    return xent + cfg["aux_loss_coef"] * aux, dropped


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up then cosine to 10% (step counts from 0)."""
    w, n = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["lr"] * (step + 1) / max(w, 1)
    prog = min(max((step - w) / max(n - w, 1), 0.0), 1.0)
    return opt["lr"] * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw(opt: dict, params, grads, m, v, lr, t):
    """One AdamW step with global-norm clipping at learning rate ``lr``;
    ``t`` is the step's number counting from 1.  Returns (params, m, v,
    clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, a, b):
        mh, vh = a / (1 - b1 ** t), b / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + opt["eps"])
                         + opt["weight_decay"] * p)
    return jax.tree.map(upd, params, m, v), m, v, grads


def served_gaps(cfg: dict, params, tokens, positions, served, *,
                capacity, quant=None, judge=None):
    """Gap, in logits, by which each served token lies below the best
    logit of the reference at the position that predicted it.

    tokens (1, S): prompt + served tokens (zero-padded); positions (n,):
    the predicting positions; served (n,): the tokens served there.
    With ``quant`` the lower-precision forward picks its own first token at
    each position instead, and its gap is read on the float32 logits
    ``judge`` (as returned by a first call).  Returns (gaps, logits at the
    positions, dropped assignments)."""
    logits, _, dropped = forward(cfg, params, tokens, capacity=capacity,
                                 quant=quant, q_block=1024)
    at = logits[0, positions]                           # (n, V)
    if quant is None:
        ref = at
        pick = served
    else:
        ref = judge
        pick = jnp.argmax(at, -1)
    gaps = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return gaps, at, dropped
