"""Operations and bytes the model needs, computed from the configuration's
published sizes.  These are the numerators of every utilization and
roofline share the benchmark reports; nothing here reads the program.

Conventions: a multiply-add is 2 FLOPs; training is forward + backward =
3x forward (recomputation does not count); causal attention over a
context of n keys costs 4 * n * H * hd per query per layer forward (QK and
PV), and a packed training row of S tokens averages n = S/2.  The dense
FFN counts its published width (10944), not the program's padded 11008.

A configuration may hold a chip's share of its routed experts: its
``n_routed_experts`` experts held here of ``published.n_routed_experts``,
ids from ``first_routed_expert`` (default 0) on.  The router scores all
``E`` published experts; the chip holds and computes ``Eh``, so a token's
``k`` picks cost it ``k * Eh / E`` experts on average.
"""
from __future__ import annotations


def routed_experts(cfg: dict):
    """(published routed experts E, the ids held here as a range)."""
    Eh = cfg["n_routed_experts"]
    E = cfg["published"].get("n_routed_experts", Eh)
    first = cfg.get("first_routed_expert", 0)
    if not 0 <= first <= first + Eh <= E:
        raise ValueError(f"experts {first}..{first + Eh - 1} held of {E}")
    return E, range(first, first + Eh)


def _sizes(cfg: dict):
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    hd = cfg["arch"]["head_dim"]
    KV = cfg["num_key_value_heads"]
    E, held = routed_experts(cfg)
    return dict(D=D, H=H, hd=hd, KV=KV, V=cfg["vocab_size"],
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                E=E, Eh=len(held), k=cfg["num_experts_per_tok"],
                ns=cfg["n_shared_experts"], nd=cfg["first_k_dense_replace"],
                nm=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def attn_params(s) -> int:
    return s["D"] * (s["H"] + 2 * s["KV"]) * s["hd"] + s["H"] * s["hd"] * s["D"]


def expert_params(s) -> int:
    """One routed expert (gate, up, down)."""
    return 3 * s["D"] * s["Fe"]


def n_params(cfg: dict) -> int:
    """All parameters held here, embedding and head included."""
    s = _sizes(cfg)
    D = s["D"]
    n = 2 * s["V"] * D + D                                  # embed, head, norm
    n += (s["nd"] + s["nm"]) * (attn_params(s) + 2 * D)
    n += s["nd"] * 3 * D * s["F"]
    n += s["nm"] * (D * s["E"] + s["Eh"] * expert_params(s)
                    + s["ns"] * expert_params(s))
    return n


def active_params(cfg: dict) -> float:
    """Parameters one token multiplies through here (the embedding is a
    lookup and the router is counted): attention, dense FFN, the k * Eh / E
    routed experts its picks land on here on average, shared experts, the
    whole router, head."""
    s = _sizes(cfg)
    D = s["D"]
    n = (s["nd"] + s["nm"]) * attn_params(s)
    n += s["nd"] * 3 * D * s["F"]
    n += s["nm"] * (s["ns"] * expert_params(s) + D * s["E"])
    n += D * s["V"]
    return n + s["nm"] * s["k"] * s["Eh"] * expert_params(s) / s["E"]


def attn_score_flops(cfg: dict, context: float) -> float:
    """Forward attention-score FLOPs of one query over ``context`` keys,
    summed over layers."""
    s = _sizes(cfg)
    return 4.0 * context * s["H"] * s["hd"] * (s["nd"] + s["nm"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Useful training FLOPs per token: 6 * active params + attention."""
    return 6.0 * active_params(cfg) + 3.0 * attn_score_flops(cfg, seq_len / 2)


def routed_expert_flops(cfg: dict, rows: float) -> float:
    """Forward FLOPs of the routed-expert FFN over ``rows`` routed rows."""
    s = _sizes(cfg)
    return 2.0 * rows * expert_params(s)


def dense_ffn_flops(cfg: dict, tokens: float) -> float:
    """Forward FLOPs of the dense layers' FFN over ``tokens`` tokens."""
    s = _sizes(cfg)
    return 2.0 * tokens * s["nd"] * 3 * s["D"] * s["F"]


def train_expert_scope(cfg: dict, tokens: int, kept_rows: float) -> dict:
    """Least work of what the trace's ``stage/expert`` scope holds in one
    training step, forward and backward (3x forward), recomputation left
    out: the routed-expert FFN on the rows it kept (padding is not useful
    work) and the dense layer's FFN, which the program scopes as
    ``stage/expert`` too.  Bytes: every expert held here and the dense-FFN
    weight read in bf16 forward and backward, and their gradients written
    in f32."""
    s = _sizes(cfg)
    flops = 3.0 * (routed_expert_flops(cfg, kept_rows)
                   + dense_ffn_flops(cfg, tokens))
    w = s["nm"] * s["Eh"] * expert_params(s) + s["nd"] * 3 * s["D"] * s["F"]
    return {"flops": flops, "bytes": w * (2 + 2 + 4)}


def serve_token_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one served token (prefilled or decoded) that
    attends over ``context`` keys."""
    return 2.0 * active_params(cfg) + attn_score_flops(cfg, context)


def w8_expert_bytes(cfg: dict) -> int:
    """Bytes of the routed experts held here in e4m3 with one f32 scale per
    128x128 block, over all MoE layers: what one forward pass that touches
    every expert reads."""
    s = _sizes(cfg)
    per = expert_params(s)
    return s["nm"] * s["Eh"] * (per + 4 * per // (128 * 128))
