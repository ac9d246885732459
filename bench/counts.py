"""Operations and bytes from shapes alone, for the utilisation and
roofline metrics.  Nothing here reads the compiled program, so a change
to the program cannot move its own yardstick.

``m`` is a configuration's ``model`` dictionary.  A matrix product of
[a, b] by [b, c] counts 2abc operations.  Embedding gathers, norms,
softmax and other elementwise work are not counted (model FLOPs).
"""
from __future__ import annotations


def matmul_params_per_layer(m: dict) -> int:
    D, H, KV, dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    n_ffn = 2 if m["activation"] == "gelu" else 3
    return D * H * dh + 2 * D * KV * dh + H * dh * D + n_ffn * D * F


def param_count(m: dict) -> int:
    """Every weight element: layers, two norm gains per layer, the final
    gain, the embedding and the untied head."""
    D, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    return L * (matmul_params_per_layer(m) + 2 * D) + D + 2 * V * D


def attention_flops(m: dict, batch: int, q_len: int, kv_len: int,
                    causal: bool) -> float:
    """Scores and the weighted sum for every layer; a causal square
    block (q_len == kv_len) counts the keys each query sees, (kv_len + 1)
    / 2 on average."""
    H, dh, L = m["n_heads"], m["head_dim"], m["n_layers"]
    keys = (kv_len + 1) / 2 if causal else kv_len
    return 4.0 * batch * q_len * keys * H * dh * L


def forward_flops(m: dict, batch: int, seq: int, logits_rows: int | None
                  = None) -> float:
    """One causal forward over [batch, seq]; ``logits_rows`` is how many
    positions go through the head (all of them when None)."""
    tokens = batch * seq
    rows = tokens if logits_rows is None else logits_rows
    dense = 2.0 * tokens * matmul_params_per_layer(m) * m["n_layers"]
    head = 2.0 * rows * m["d_model"] * m["vocab_size"]
    return dense + head + attention_flops(m, batch, seq, seq, True)


def zo_step_flops(m: dict, batch: int, seq: int, q: int) -> float:
    """Model FLOPs of one ZO step: its 2q forwards."""
    return 2 * q * forward_flops(m, batch, seq)


def _leaves(m: dict):
    """(batch, rows, cols) of every parameter leaf as the program holds
    it; 1-D leaves have rows = 1."""
    L, D, V = m["n_layers"], m["d_model"], m["vocab_size"]
    H, KV, dh, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    out = [(1, V, D), (1, D, V), (1, 1, D),      # embed, lm_head, final_norm
           (1, L, D), (1, L, D),                 # ln1, ln2 as [L, D]
           (L, D, H * dh), (L, D, KV * dh), (L, D, KV * dh), (L, H * dh, D),
           (L, D, F), (L, F, D)]
    if m["activation"] != "gelu":
        out.append((L, D, F))
    return out


def zo_pass_cost(m: dict, q: int, rank: int, weight_bytes: int = 2,
                 factor_bytes: int = 4) -> dict:
    """Least work of the 2q+1 weight passes of one chained TeZO-Adam step
    (first perturb, q flips, q - 1 bridges, the restore folded into the
    update), summed over leaves.

    Bytes: each pass reads and writes every weight once and reads each
    low-rank leaf's factors u [m, r] and v [n, r]; a leaf with a dimension
    under 8 takes dense noise and has no factors.  Operations: a delta
    (u diag tau) v^T costs 2r per element, plus 2 to scale and add; the
    update pass costs a restore delta, then 2r for M and 2r for V plus 4
    for the normalised step.  A pass moves 4 bytes an element against at
    most 12r + 16 operations, far under the chip's ridge, so the bytes set
    the bound.
    """
    passes = 2 * q + 1
    bytes_ = flops = 0.0
    for b, rows, cols in _leaves(m):
        n = b * rows * cols
        lowrank = rows >= 8 and cols >= 8
        r = min(rank, rows, cols)
        bytes_ += passes * 2 * weight_bytes * n
        if lowrank:
            bytes_ += passes * b * (rows + cols) * r * factor_bytes
            delta = (2 * r + 2) * n
            # the first perturb and q flips apply one delta each, the
            # q - 1 bridges two; the update: restore delta, M, V and 4
            flops += (1 + q + 2 * (q - 1)) * delta
            flops += delta + (4 * r + 4) * n
        else:
            flops += (3 * q + 1) * 2 * n + 6 * n
    return {"bytes": bytes_, "flops": flops, "passes": passes}


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])


def decode_attention_cost(m: dict, context: int, kv_bytes: int = 2):
    """(operations, bytes) of one token's attention over ``context``
    cached positions in every layer: scores and weighted sum, 4 H dh per
    position, and the keys and values read once."""
    H, KV, dh, L = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["n_layers"]
    flops = 4.0 * H * dh * context * L
    bytes_ = 2.0 * KV * dh * kv_bytes * context * L
    return flops, bytes_


def decode_flops(m: dict, context: int) -> float:
    """One decoded token: the layers' products, the head, and attention
    over ``context`` positions."""
    dense = 2.0 * matmul_params_per_layer(m) * m["n_layers"]
    head = 2.0 * m["d_model"] * m["vocab_size"]
    return dense + head + decode_attention_cost(m, context)[0]
