"""The dense GQA decoder (Qwen3 / InternLM2 style): its weight layout, the
program's configuration, the plain float32 reference and the work counts.

Architecture modules whose model is this decoder re-export it; one with a
departure imports it and replaces what differs.

Reference: token embedding; per layer RMSNorm → q/k/v projections,
optional per-head RMSNorm of q and k (Qwen3's ``q_norm``/``k_norm``),
rotary embedding (rotate-half, ``rope_theta``), causal softmax attention
with ``num_attention_heads / num_key_value_heads`` query heads per key
head, output projection, residual; RMSNorm → SwiGLU FFN, residual; final
RMSNorm and the head (the embedding's transpose when tied). RMSNorm is
``x / sqrt(mean(x²) + eps) · (1 + g)``, the gain convention of the
weights' layout.

Work counts: "useful" work only, the products the algorithm needs for the
tokens served, not what an implementation pads or recomputes. A matrix
product of ``m × k`` by ``k × n`` is ``2·m·k·n`` operations. Causal
prefill attention counts the ``S·(S+1)/2`` query-key pairs at or below
the diagonal; the prefill's head runs for the last position only, as the
program's ``prefill`` does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.flops import DTYPE_BYTES
from chipbench.reference import _mm, _rms, _rope
from chipbench.weights import EMBED_STD, NORM_STD

__all__ = ["decode_attn_bytes_per_layer", "decode_attn_flops_per_layer", "decode_flops", "dims",
           "head", "hidden", "kv_bytes_per_token", "layout", "model_config", "param_count",
           "prefill_flops"]


def dims(c: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    return {
        "d": c["hidden_size"], "L": c["num_hidden_layers"], "H": c["num_attention_heads"],
        "KH": c["num_key_value_heads"], "hd": c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        "ff": c["intermediate_size"], "V": c["vocab_size"],
    }


def layout(c: dict) -> dict:
    """``{path: (shape, std, fan_in_axes)}`` of every parameter, where
    ``fan_in_axes`` are the contracted axes (None for an embedding or gain)."""
    z = dims(c)
    d, L, H, KH, hd, ff, V = (z[k] for k in ("d", "L", "H", "KH", "hd", "ff", "V"))
    out = {
        ("embed",): ((V, d), EMBED_STD, None),
        ("final_norm",): ((d,), NORM_STD, None),
        ("layers", "b0", "norm"): ((L, d), NORM_STD, None),
        ("layers", "b0", "attn", "wq"): ((L, d, H, hd), None, (1,)),
        ("layers", "b0", "attn", "wk"): ((L, d, KH, hd), None, (1,)),
        ("layers", "b0", "attn", "wv"): ((L, d, KH, hd), None, (1,)),
        ("layers", "b0", "attn", "wo"): ((L, H, hd, d), None, (1, 2)),
        ("layers", "b0", "ffn_norm"): ((L, d), NORM_STD, None),
        ("layers", "b0", "ffn", "gate"): ((L, d, ff), None, (1,)),
        ("layers", "b0", "ffn", "up"): ((L, d, ff), None, (1,)),
        ("layers", "b0", "ffn", "down"): ((L, ff, d), None, (1,)),
    }
    if c["qk_norm"]:
        out[("layers", "b0", "attn", "q_norm")] = ((L, hd), NORM_STD, None)
        out[("layers", "b0", "attn", "k_norm")] = ((L, hd), NORM_STD, None)
    if not c["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, V), None, (0,))
    return out


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from chipbench.system import ModelConfig

    return ModelConfig(
        name=c["name"],
        family="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", 0),
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        qk_norm=c["qk_norm"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"],
        source=c["source"],
    )


def hidden(c: dict, w: dict, tokens: jax.Array, fp8: bool = False) -> jax.Array:
    """Final-normed hidden states (S, d) of one sequence of token ids."""
    z = dims(c)
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    groups = z["H"] // z["KH"]
    s = tokens.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def layer(h, p):
        a = p["attn"]
        x = _rms(h, p["norm"], eps)
        q = _mm("sd,dhk->shk", x, a["wq"], fp8)
        k = _mm("sd,dhk->shk", x, a["wk"], fp8)
        v = _mm("sd,dhk->shk", x, a["wv"], fp8)
        if c["qk_norm"]:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, groups, axis=1)  # query head i reads key head i // groups
        v = jnp.repeat(v, groups, axis=1)
        scores = _mm("qhk,shk->hqs", q, k, fp8, w_is_weight=False) / jnp.sqrt(float(z["hd"]))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out = _mm("hqs,shk->qhk", probs, v, fp8, w_is_weight=False)
        h = h + _mm("qhk,hkd->qd", out, a["wo"], fp8)
        f = p["ffn"]
        x = _rms(h, p["ffn_norm"], eps)
        gate = _mm("sd,df->sf", x, f["gate"], fp8)
        up = _mm("sd,df->sf", x, f["up"], fp8)
        return h + _mm("sf,fd->sd", jax.nn.silu(gate) * up, f["down"], fp8), None

    h = w["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(layer, h, w["layers"]["b0"])
    return _rms(h, w["final_norm"], eps)


def head(c: dict, w: dict) -> jax.Array:
    """The (d, vocab) matrix the final hidden states are read through."""
    return w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]


def param_count(c: dict) -> int:
    return sum(math.prod(shape) for shape, _, _ in layout(c).values())


def layer_matmul_params(c: dict) -> int:
    z = dims(c)
    attn = z["d"] * z["hd"] * (2 * z["H"] + 2 * z["KH"])
    return attn + 3 * z["d"] * z["ff"]


def head_params(c: dict) -> int:
    z = dims(c)
    return z["d"] * z["V"]


def kv_bytes_per_token(c: dict) -> int:
    z = dims(c)
    return 2 * z["L"] * z["KH"] * z["hd"] * DTYPE_BYTES[c["compute_dtype"]]


def prefill_flops(c: dict, s: int) -> int:
    """One prompt of ``s`` tokens: projections and FFN for every token,
    causal attention, the head for the last position."""
    z = dims(c)
    attn = 4 * z["H"] * z["hd"] * (s * (s + 1) // 2)
    return z["L"] * (2 * layer_matmul_params(c) * s + attn) + 2 * head_params(c)


def decode_flops(c: dict, valid: list[int]) -> int:
    """One decode step over active rows whose caches hold ``valid[i]``
    positions each (the new token's included)."""
    n_layers = dims(c)["L"]
    return (len(valid) * 2 * (n_layers * layer_matmul_params(c) + head_params(c))
            + n_layers * decode_attn_flops_per_layer(c, valid))


def decode_attn_flops_per_layer(c: dict, valid: list[int]) -> int:
    z = dims(c)
    return 4 * z["H"] * z["hd"] * sum(valid)


def decode_attn_bytes_per_layer(c: dict, valid: list[int]) -> int:
    """The keys and values of each active row's valid positions, its query
    and its output, in the compute dtype: what any implementation of one
    layer's decode attention must move."""
    z = dims(c)
    e = DTYPE_BYTES[c["compute_dtype"]]
    return e * (2 * z["KH"] * z["hd"] * sum(valid) + 2 * z["H"] * z["hd"] * len(valid))
