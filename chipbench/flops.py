"""Operations and bytes of the work the program is asked to do, from
sizes alone. These are the numerators of every roofline and utilisation
share the benchmark reports, kept here so that no change to the program
can move them.

Counts are of useful work, never of padding: a kernel that pads its
operands does more than this, so a share built on these counts stays at
or below 100% whenever the peak rates are right.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    stat_chunks: int = 16      # chunk-mass columns the chunk kernel fuses
    bytes_per_el: int = 4      # float32 activations and KV

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(layers=cfg["num_hidden_layers"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"],
                   d_ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"])


def layer_matmul_flops(m: Dims) -> int:
    """Matmul FLOPs of one token through one layer: q, k, v and output
    projections and the SwiGLU feed-forward (gate, up, down)."""
    d, h, hkv, dh, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return 2 * (d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f)


def attention_flops(m: Dims, queries: int, keys: int) -> int:
    """Score and value products of ``queries`` rows against ``keys``
    keys, every layer: 4 * q * k * H * Dh per layer."""
    return 4 * queries * keys * m.heads * m.head_dim * m.layers


def head_flops(m: Dims, rows: int = 1) -> int:
    return 2 * m.d_model * m.vocab * rows


def prefill_flops(m: Dims, computed: int, total: int) -> int:
    """Model FLOPs of a partial prefill that computes ``computed`` of a
    prompt's ``total`` tokens: the layers for each computed token, causal
    attention for each over half the prompt on average, and the head for
    the last token."""
    return (computed * m.layers * layer_matmul_flops(m)
            + attention_flops(m, computed, (total + 1) // 2)
            + head_flops(m))


def decode_flops(m: Dims, context: int) -> int:
    """Model FLOPs of one decoded token that attends to ``context``
    positions (itself included)."""
    return (m.layers * layer_matmul_flops(m)
            + attention_flops(m, 1, context) + head_flops(m))


def chunk_attn_work(m: Dims, queries: int, keys: int):
    """(FLOPs, bytes) of the chunk-attention kernel over ``queries``
    active rows and ``keys`` merged keys, every layer: the two attention
    products over the whole query-by-key rectangle the kernel tiles, the
    fused chunk-mass product, and one read of q, k and v and one write
    of the output. The mass product is counted once over the summed
    heads, the least it takes."""
    fl = attention_flops(m, queries, keys) + \
        2 * queries * keys * m.stat_chunks * m.layers
    by = m.bytes_per_el * m.layers * (
        2 * queries * m.heads * m.head_dim
        + 2 * keys * m.kv_heads * m.head_dim)
    return fl, by


def paged_decode_work(m: Dims, context: int):
    """(FLOPs, bytes) of the paged decode kernel for one query row that
    attends to ``context`` cached positions, every layer: one read of
    that row's K and V and of q, one write of the output."""
    fl = attention_flops(m, 1, context)
    by = m.bytes_per_el * m.layers * (
        2 * context * m.kv_heads * m.head_dim + 2 * m.heads * m.head_dim)
    return fl, by


def roofline_seconds(flops: float, nbytes: float, peak) -> float:
    """The least time the chip could take: operations over peak FLOP/s
    or bytes over peak bandwidth, whichever is longer."""
    return max(flops / peak.flops_bf16, nbytes / peak.hbm_bytes_s)
