"""Useful work of the served model's steps, counted from shapes.

The counts follow the published architecture, whatever implements it:
live rows and real context lengths (never padded ones), the LM head only
at positions that yield a token, and for a mixture of experts only the
routed top-k and the shared experts.  So removing padding, or the dense
expert fallback, can only raise a share of the peak computed from these
counts, and the counts can never make it pass 100%.  They take the
configuration file's published keys and nothing from the program.
"""

from __future__ import annotations

BF16 = 2  # bytes per weight and cache element as served


def _mlp(d: int, f: int) -> int:
    return 3 * d * f  # SwiGLU: gate, up, down


def _attn(conf: dict) -> int:
    d, n = conf["hidden_size"], conf["num_attention_heads"]
    if "kv_lora_rank" in conf:  # MLA, no query compression
        r, pr = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
        pn, hv = conf["qk_nope_head_dim"], conf["v_head_dim"]
        return (d * n * (pn + pr) + d * (r + pr) + r
                + r * n * (pn + hv) + n * hv * d)
    h = d // n
    return d * n * h + 2 * d * conf["num_key_value_heads"] * h + n * h * d


def layers(conf: dict) -> list[str]:
    """Kind of each layer in order: "dense" or "moe"."""
    n = conf["num_hidden_layers"]
    if "n_routed_experts" not in conf:
        return ["dense"] * n
    k = conf["first_k_dense_replace"]
    return ["dense"] * k + ["moe"] * (n - k)


def expert_params(conf: dict) -> int:
    return _mlp(conf["hidden_size"], conf["moe_intermediate_size"])


def params(conf: dict) -> dict:
    """Parameter counts: ``embed``, ``head``, ``layers`` (everything in
    the layers but the routed experts), ``routed`` (all routed experts)."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    body = routed = 0
    for kind in layers(conf):
        body += _attn(conf) + 2 * d
        if kind == "moe":
            e = conf["n_routed_experts"]
            routed += e * expert_params(conf)
            body += conf["n_shared_experts"] * expert_params(conf) + d * e
        else:
            body += _mlp(d, conf["intermediate_size"])
    head = 0 if conf["tie_word_embeddings"] else d * v
    return {"embed": v * d, "head": head, "layers": body + d,
            "routed": routed}


def total_params(conf: dict) -> int:
    return sum(params(conf).values())


def kv_bytes_per_token(conf: dict) -> int:
    n = conf["num_hidden_layers"]
    if "kv_lora_rank" in conf:
        return n * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * BF16
    h = conf["hidden_size"] // conf["num_attention_heads"]
    return n * 2 * conf["num_key_value_heads"] * h * BF16


def _active_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in the layers (routed
    experts: only its top-k)."""
    p = params(conf)
    act = p["layers"]
    for kind in layers(conf):
        if kind == "moe":
            act += conf["num_experts_per_tok"] * expert_params(conf)
    return act


def _attn_flops(conf: dict, ctx: int) -> float:
    """Score and value matmuls of one query position over ``ctx`` keys."""
    n = conf["num_attention_heads"]
    if "kv_lora_rank" in conf:
        qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        vd = conf["v_head_dim"]
    else:
        qk = vd = conf["hidden_size"] // n
    return 2.0 * n * ctx * (qk + vd) * conf["num_hidden_layers"]


def decode_token_flops(conf: dict, ctx: int) -> float:
    """One decode token whose attention reads ``ctx`` positions (its own
    included), plus the LM head at that position."""
    head = conf["hidden_size"] * conf["vocab_size"]
    return (2.0 * (_active_matmul_params(conf) + head)
            + _attn_flops(conf, ctx))


def prefill_flops(conf: dict, length: int) -> float:
    """A prompt of ``length`` real tokens, causal, with the LM head at its
    last position only (the one that yields the first token)."""
    head = conf["hidden_size"] * conf["vocab_size"]
    attn = sum(_attn_flops(conf, i + 1) for i in range(length))
    return 2.0 * (_active_matmul_params(conf) * length + head) + attn


def decode_call_bytes(conf: dict, contexts: list[int]) -> float:
    """Least HBM traffic of one decode call over live rows with these
    contexts: every weight but the routed experts once, at most
    min(E, rows * k) experts per MoE layer, the embedding rows of the
    tokens, each row's cache read and one new cache row written."""
    p = params(conf)
    rows = len(contexts)
    w = p["layers"] + p["head"]
    for kind in layers(conf):
        if kind == "moe":
            used = min(conf["n_routed_experts"],
                       rows * conf["num_experts_per_tok"])
            w += used * expert_params(conf)
    kv = kv_bytes_per_token(conf)
    return (BF16 * (w + rows * conf["hidden_size"])
            + kv * (sum(contexts) + rows))
