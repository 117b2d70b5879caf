"""Tiny stand-ins for the benchmark's cells, for runs on the CPU.

``shrink(cell)`` cuts a resolved cell to CPU size in place: the same
structure (a dense layer, MoE layers with shared experts, FP8 recipe, W8
experts and FP8 pages when serving), every width small, the FFN width not
a multiple of 128 (so the zero-padding path runs), short sequences.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def shrink_cfg(cfg: dict, layers: int = None) -> None:
    cfg.update({"hidden_size": 256, "num_attention_heads": 4,
                "num_key_value_heads": 4, "intermediate_size": 320,
                "moe_intermediate_size": 128, "n_routed_experts": 8,
                "num_experts_per_tok": 2, "n_shared_experts": 1,
                "vocab_size": 512})
    if layers:
        cfg["num_hidden_layers"] = layers
    cfg["arch"].update({"d_model": 256, "n_heads": 4, "n_kv": 4,
                        "head_dim": 64, "d_ff": 384, "vocab": 512,
                        "n_experts": 8, "top_k": 2, "d_ff_expert": 128,
                        "n_shared_experts": 1,
                        "n_layers": cfg["num_hidden_layers"]})


# limits for the tiny training cell under the bf16 recipe, from CPU readings
# of the program (loss 5e-5..6e-5, grad 1.0e-3..1.4e-3, change 6e-4..1.1e-3)
# and of the int4 control (grad 0.025..0.060)
TINY_TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 1.5e-2, "change_gap": 1.5e-2}


def shrink(cell) -> None:
    shrink_cfg(cell.cfg)
    m = cell.mix
    if m["kind"] == "train":
        m.update({"batch": 2, "seq_len": 512})
        m["doc_len"].update({"median": 48, "min": 8, "max": 400})
    else:
        m["prompt_len"].update({"median": 30, "min": 8, "max": 60})
        m["output_len"].update({"median": 6, "min": 3, "max": 10})
        m["engine"].update({"max_batch": 4, "max_len": 160,
                            "prefill_buckets": [16, 32, 64],
                            "prefill_chunk": 64})
        cell.cell["rate"] = 6.0


def shrink_bf16(cell) -> None:
    """The tiny training cell on the program's bf16 recipe, whose expert
    capacity rounds to 8 rows (the FP8 recipes round to 128)."""
    shrink(cell)
    cell.cfg["recipe"] = "bf16"
    cell.cfg["expert_capacity_multiple"] = 8
    cell.cell["limits"] = dict(TINY_TRAIN_LIMITS)
