"""Operations and bytes that the served model's work requires, as functions
of shapes alone.

Every per-layer metric that divides work by time takes its numerator from
here.  The counts are what the algorithm needs, not what a kernel happens
to do: a packed matrix counts as its dense matmul (2·M·K·N), padding rows
of a prefill chunk count nothing, and the per-step quantize-dequantize of
the embedding counts nothing, since no output needs it.

``cfg`` is a configuration file's dict (``configs/<name>.json``).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Linear:
    name: str
    K: int      # contraction width
    N: int      # output width
    bits: int   # packed weight bits


def layer_linears(cfg) -> list[Linear]:
    """The packed matmuls of one decoder layer, in call order."""
    D, H, KV, hd, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    b = cfg["policy"]["weight_bits"]
    return [Linear("wq", D, H * hd, b), Linear("wk", D, KV * hd, b),
            Linear("wv", D, KV * hd, b), Linear("wo", H * hd, D, b),
            Linear("wg", D, F, b), Linear("wu", D, F, b),
            Linear("wd", F, D, b)]


def head_linear(cfg) -> Linear:
    return Linear("lm_head", cfg["d_model"], cfg["vocab_size"],
                  cfg["policy"]["frozen_bits"])


def qmm_flops(M: int, lin: Linear) -> float:
    return 2.0 * M * lin.K * lin.N


def qmm_bytes(M: int, lin: Linear) -> float:
    """Bit planes + per-column f32 scales + bf16 activations + f32 output."""
    planes = lin.bits * (lin.K // 8) * lin.N
    return planes + 4.0 * lin.N + 2.0 * M * lin.K + 4.0 * M * lin.N


def qmm_least_seconds(M: int, lin: Linear, peak) -> float:
    """The least time one call can take on the chip: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(qmm_flops(M, lin) / peak.bf16_flops,
               qmm_bytes(M, lin) / peak.hbm_bytes_s)


def step_qmm_calls(cfg, M: int, with_head: bool = True) -> list[tuple[int, Linear]]:
    """(rows, matrix) of every qmm call in one executable whose activations
    have ``M`` rows: every layer's matrices, then the head unless
    ``with_head`` is false."""
    calls = [(M, lin) for _ in range(cfg["num_layers"])
             for lin in layer_linears(cfg)]
    if with_head:
        calls.append((M, head_linear(cfg)))
    return calls


def decode_qmm_calls(cfg) -> list[tuple[int, Linear]]:
    """A decode step runs every matrix, head included, on all slots' rows."""
    return step_qmm_calls(cfg, cfg["engine"]["num_slots"])


def prefill_qmm_calls(cfg) -> list[tuple[int, Linear]]:
    """A prefill chunk runs the layers on ``prefill_chunk`` rows and the
    head on the last valid row only."""
    calls = step_qmm_calls(cfg, cfg["engine"]["prefill_chunk"], with_head=False)
    calls.append((1, head_linear(cfg)))
    return calls


def kv_bytes_per_token(cfg) -> float:
    """Bytes one cached token holds per layer: K and V codes and their
    per-(token, head) f32 scales."""
    KV, hd, bits = cfg["num_kv_heads"], cfg["head_dim"], cfg["policy"]["kv_bits"]
    codes = hd // 2 if bits == 4 else hd
    return 2.0 * KV * (codes + 4.0)


def decode_attn_bytes(cfg, lengths) -> float:
    """Least bytes one decode step's paged attention reads over all layers:
    each row's live KV (``lengths`` tokens, the new one included), plus the
    bf16 queries and outputs of the rows."""
    rows = cfg["engine"]["num_slots"]
    H, hd, L = cfg["num_heads"], cfg["head_dim"], cfg["num_layers"]
    live = sum(lengths) * kv_bytes_per_token(cfg)
    return L * (live + 2.0 * 2.0 * rows * H * hd)


def decode_attn_flops(cfg, lengths) -> float:
    H, hd, L = cfg["num_heads"], cfg["head_dim"], cfg["num_layers"]
    return 4.0 * H * hd * L * sum(lengths)


def linear_flops_per_token(cfg) -> float:
    """Matmul FLOPs of the layers for one token (head excluded)."""
    return sum(2.0 * lin.K * lin.N for lin in layer_linears(cfg)) * cfg["num_layers"]


def head_flops(cfg) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab_size"]


def prefill_chunk_flops(cfg, start: int, valid: int) -> float:
    """FLOPs a chunk of ``valid`` prompt tokens at positions ``start`` ..
    ``start + valid - 1`` requires: the layers for every token, causal
    attention over each token's own prefix, and the head for the last."""
    ctx = valid * start + valid * (valid + 1) / 2.0   # sum of (start + i + 1)
    return (valid * linear_flops_per_token(cfg) + head_flops(cfg)
            + 4.0 * cfg["num_heads"] * cfg["head_dim"] * cfg["num_layers"] * ctx)


def decode_step_flops(cfg, lengths) -> float:
    """FLOPs a decode step requires: one token for each running row, whose
    attention reads ``lengths`` keys."""
    n = len(lengths)
    return (n * (linear_flops_per_token(cfg) + head_flops(cfg))
            + decode_attn_flops(cfg, lengths))
