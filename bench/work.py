"""The work a problem requires, computed from its shapes: operations and
bytes, for the roofline shares.  Counted from the problem, never from
what an implementation happens to do (a padded gather, a converted
layout), so that every implementation is read against one yardstick."""
from __future__ import annotations

from typing import Sequence, Tuple

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4, "int8": 1}


# ---------------------------------------------------------------------------
# a dense decoder (qwen2-shaped configuration files)
# ---------------------------------------------------------------------------

def decoder_params(cfg: dict) -> int:
    """Parameters of a GQA decoder with gated MLP, as the configuration
    file states it (published vocabulary, tied or untied head)."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    if cfg.get("attention_bias", cfg.get("model_type") == "qwen2"):
        attn += q + 2 * kv
    layer = attn + 3 * d * f + 2 * d
    embed = cfg["vocab_size"] * d
    head = 0 if cfg.get("tie_word_embeddings") else cfg["vocab_size"] * d
    return L * layer + embed + head + d


def kv_bytes_per_position(cfg: dict, dtype: str) -> int:
    """K and V of one position over all layers."""
    hd = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
            * BYTES[dtype])


def decode_step_work(cfg: dict, contexts: Sequence[int],
                     dtype: str) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step requires for a live batch whose
    requests attend over ``contexts`` positions each: the weights read
    once, each request's live K/V read once, its new K/V and its logits
    row written.  FLOPs are 2 per parameter per token (the embedding
    lookup is no multiply, the tied head is) plus QK and PV over the live
    context."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    hq = cfg["num_attention_heads"]
    b = len(contexts)
    w = BYTES[dtype]
    params = decoder_params(cfg)
    matmul_params = params - cfg["vocab_size"] * d * \
        (0 if cfg.get("tie_word_embeddings") else 1)
    flops = 2.0 * matmul_params * b + \
        sum(4.0 * c * hq * hd * L for c in contexts)
    kv = kv_bytes_per_position(cfg, dtype)
    bytes_ = (params * w + sum(contexts) * kv + b * kv
              + b * cfg["vocab_size"] * w)
    return flops, float(bytes_)


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# compiler programs
# ---------------------------------------------------------------------------

def spmv_csr_bytes(rows: int, cols: int, nnz: int,
                   dtype: str = "float32") -> float:
    """CSR SpMV reads the row pointers, the column indices and the
    values once, x once, and writes y once."""
    w = BYTES[dtype]
    return float((rows + 1) * 4 + nnz * 4 + nnz * w + cols * w + rows * w)


def spmv_csr_flops(nnz: int) -> float:
    return 2.0 * nnz


def swiglu_mlp_work(t: int, d: int, f: int,
                    dtype: str) -> Tuple[float, float]:
    """``(silu(x @ gate) * (x @ up)) @ down`` with x (t, d), gate and up
    (d, f), down (f, d): FLOPs of the three products, bytes of the
    operands read once and the result written once."""
    w = BYTES[dtype]
    flops = 2.0 * t * d * f * 3
    bytes_ = float((t * d + 3 * d * f + t * d) * w)
    return flops, bytes_
