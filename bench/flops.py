"""Operations and bytes that the model's work needs, from the sizes in a
configuration file of ``bench/configs`` alone.

These are the numerators of every roofline and ``mfu`` share: the least
work the algorithm needs, not what the program happens to do (padding
rows, recomputation and gathered views beyond the live cache count for
nothing). Matrix multiplications count 2 operations per multiply-add.
"""
from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    d, h, kv, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                       c["head_dim"], c["d_ff"])
    attn = d * hd * (h + 2 * kv) + h * hd * d
    mlp = (3 if c["act"] == "swiglu" else 2) * d * f
    return attn + mlp


def layer_norm_params(c: dict) -> int:
    # rms norms carry a weight; layer norms a weight and a bias
    return (4 if c["norm"] == "layer" else 2) * c["d_model"]


def param_counts(c: dict) -> dict:
    d, v = c["d_model"], c["vocab_size"]
    out = {
        "layers": c["n_layers"] * (layer_matmul_params(c)
                                   + layer_norm_params(c)),
        "embed": v * d,
        "pos": c.get("max_positions", 0) * d,
        "head": 0 if c.get("tie_embeddings") else d * v,
        "final_norm": (2 if c["norm"] == "layer" else 1) * d,
    }
    out["total"] = sum(out.values())
    return out


def weight_bytes(c: dict, bytes_per_param: int) -> int:
    return param_counts(c)["total"] * bytes_per_param


def kv_bytes_per_token(c: dict, bytes_per_elem: int) -> int:
    return c["n_layers"] * 2 * c["n_kv_heads"] * c["head_dim"] * bytes_per_elem


def token_flops(c: dict, n_keys: int) -> float:
    """Forward operations of one token that attends to ``n_keys`` keys,
    without the output head."""
    attn = 4 * c["n_heads"] * c["head_dim"] * n_keys
    return c["n_layers"] * (2 * layer_matmul_params(c) + attn)


def head_flops(c: dict) -> float:
    return 2 * c["d_model"] * c["vocab_size"]


def prefill_flops(c: dict, start: int, n: int) -> float:
    """Forward operations of ``n`` prompt tokens at positions start..start+n-1
    of one causal row (each attends to itself and every earlier position)."""
    keys = n * start + n * (n + 1) // 2
    attn = 4 * c["n_heads"] * c["head_dim"] * keys
    return c["n_layers"] * (2 * layer_matmul_params(c) * n + attn)


def serve_call_roofline_s(c: dict, rows: list, peaks: dict,
                          weight_bytes_per_param: int,
                          cache_bytes_per_elem: int) -> float:
    """The least time one serve call over ``rows`` can take on a chip.

    ``rows`` holds (start, n, emits) per row that does real work: ``n`` new
    tokens written at positions start.., and whether the row emits a token
    (which needs the head). The call must read every weight but the
    embedding table once, plus each row's live cache; it must compute each
    row's tokens. The roofline is the larger of the two times."""
    pc = param_counts(c)
    wbytes = (pc["total"] - pc["embed"] - pc["pos"]) * weight_bytes_per_param
    kvb = kv_bytes_per_token(c, cache_bytes_per_elem)
    nbytes = wbytes + sum((s + n) * kvb for s, n, _ in rows)
    ops = sum(prefill_flops(c, s, n) + (head_flops(c) if e else 0.0)
              for s, n, e in rows)
    return max(nbytes / peaks["hbm_bytes_per_s"], ops / peaks["flops_bf16"])


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward operations per training token (3x forward), no
    recomputation: every layer, the attention over the row (all ``seq``
    keys for an encoder, the causal half otherwise) and the output head."""
    keys = seq if not c["causal"] else (seq + 1) / 2
    return 3 * (token_flops(c, keys) + head_flops(c))
