"""Algorithmic operations and bytes from shapes, per model family.

What the model's mathematics needs, not what the compiled program does:
no recomputation, no padding, no masked-out work.  A multiply-add is two
operations.  Conventions:

* matmul parameters are every weight a token multiplies, the output head
  included and the embedding lookup excluded;
* causal attention counts each query against the keys at and before it,
  (S + 1) / 2 keys on average over a sequence of S, for Q·K and for P·V;
* the SSD scan (Mamba2) counts the chunked dual form of arXiv:2405.21060
  Sec. 6 at the configuration's chunk length L: C·Bᵀ per group (2 L N per
  token), the masked product with X per head (2 L P), the chunk states and
  their read-out per head (2 N P each); the depthwise convolution counts
  2 · width per channel;
* training is three forward passes (the backward pass is two).
"""
from __future__ import annotations

from typing import Dict, Iterable


def shapes(cfg: Dict) -> Dict[str, int]:
    """The sizes the counts below need, from a configuration file."""
    if cfg["family"] == "dense":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return {"family": "dense", "d": d, "layers": cfg["num_hidden_layers"],
                "heads": h, "kv_heads": cfg["num_key_value_heads"],
                "head_dim": cfg.get("head_dim") or d // h,
                "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}
    if cfg["family"] == "ssm":
        d, e, p = cfg["d_model"], cfg["expand"], cfg["headdim"]
        return {"family": "ssm", "d": d, "layers": cfg["n_layer"],
                "inner": e * d, "heads": e * d // p, "head_dim": p,
                "state": cfg["d_state"], "groups": cfg["ngroups"],
                "conv": cfg["d_conv"], "chunk": cfg["chunk_size"],
                "vocab": cfg["vocab_size"]}
    raise ValueError(f"no operation count for family {cfg['family']!r}")


def matmul_params(cfg: Dict) -> Dict[str, int]:
    """Weights a token multiplies: ``layers`` (all layers) and ``head``."""
    s = shapes(cfg)
    d = s["d"]
    if s["family"] == "dense":
        q = d * s["heads"] * s["head_dim"]
        kv = 2 * d * s["kv_heads"] * s["head_dim"]
        o = s["heads"] * s["head_dim"] * d
        mlp = 3 * d * s["ff"]
        per_layer = q + kv + o + mlp
    else:
        in_proj = d * (2 * s["inner"] + 2 * s["groups"] * s["state"] + s["heads"])
        per_layer = in_proj + s["inner"] * d
    return {"layers": per_layer * s["layers"], "head": d * s["vocab"]}


def mixer_forward_per_token(cfg: Dict, context: float) -> float:
    """Forward operations per token of the sequence mixer over all layers:
    attention at an average of ``context`` keys per query, or the SSD scan
    and convolution (independent of the context)."""
    s = shapes(cfg)
    if s["family"] == "dense":
        return s["layers"] * 4.0 * s["heads"] * s["head_dim"] * context
    L, n, p, h, g = s["chunk"], s["state"], s["head_dim"], s["heads"], s["groups"]
    conv_ch = s["inner"] + 2 * g * n
    per_layer = 2 * L * n * g + 2 * L * p * h + 4 * n * p * h + 2 * s["conv"] * conv_ch
    return s["layers"] * float(per_layer)


def forward_flops(cfg: Dict, batch: int, seq_len: int) -> float:
    """One forward pass with logits at every position."""
    mp = matmul_params(cfg)
    tokens = batch * seq_len
    return tokens * (2.0 * (mp["layers"] + mp["head"])
                     + mixer_forward_per_token(cfg, (seq_len + 1) / 2))


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward operations per trained token (6N + mixer)."""
    mp = matmul_params(cfg)
    return (6.0 * (mp["layers"] + mp["head"])
            + 3.0 * mixer_forward_per_token(cfg, (seq_len + 1) / 2))


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    """Prefill of one prompt: every layer at every position, the head at
    the last one."""
    mp = matmul_params(cfg)
    return (prompt_len * (2.0 * mp["layers"]
                          + mixer_forward_per_token(cfg, (prompt_len + 1) / 2))
            + 2.0 * mp["head"])


def decode_flops(cfg: Dict, context: int) -> float:
    """One decoded token whose query sees ``context`` keys (itself included)."""
    mp = matmul_params(cfg)
    return 2.0 * (mp["layers"] + mp["head"]) + mixer_forward_per_token(cfg, context)


def weight_bytes(cfg: Dict, bytes_per_weight: int = 2) -> float:
    mp = matmul_params(cfg)
    return float(bytes_per_weight * (mp["layers"] + mp["head"]))


def decode_step_bytes(cfg: Dict, contexts: Iterable[int],
                      bytes_per_value: int = 2) -> float:
    """HBM bytes one lockstep decode step needs: every matmul weight once,
    and each live request's cached keys and values at its own length (for
    the SSM family, its conv window read and its float32 scan state read
    and written instead)."""
    s = shapes(cfg)
    total = weight_bytes(cfg, bytes_per_value)
    for ctx in contexts:
        if s["family"] == "dense":
            total += (2 * s["layers"] * s["kv_heads"] * s["head_dim"]
                      * ctx * bytes_per_value)
        else:
            conv_ch = s["inner"] + 2 * s["groups"] * s["state"]
            total += s["layers"] * ((s["conv"] - 1) * conv_ch * bytes_per_value
                                    + 8 * s["heads"] * s["head_dim"] * s["state"])
    return total
