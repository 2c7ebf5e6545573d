"""Operations and bytes that a Mamba-2/attention hybrid's steps and its
fused state update need, from shapes (`chipbench.counts` for the dense
decoder).

These are the algorithm's counts, not the compiler's.  Every function
takes the configuration dict as ``chipbench/configs/*.json`` holds it (the
keys of the source's ``config.json``) and plain sizes.  Weights, K/V, the
recurrent state and its conv window are in bfloat16, the kernel's
per-token inputs and outputs in float32, as the program holds them.
"""
from __future__ import annotations

from typing import Dict, Iterable

from chipbench.counts import descriptor_store_bytes
from chipbench.reference.hybrid import dims

BF16 = 2
F32 = 4


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind: ``mamba`` and ``attention``."""
    kinds = list(cfg["layer_types"])
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def matmul_params(cfg: Dict, kind: str) -> int:
    """Weights of one layer that multiply every token: the mixer's
    projections (and the Mamba conv's taps) and the SwiGLU MLP."""
    m = dims(cfg)
    d = m["d"]
    mlp = 3 * d * m["f"]
    if kind == "mamba":
        return (d * (m["di"] + m["cd"] + m["H"]) + m["W"] * m["cd"]
                + m["di"] * d + mlp)
    hd = m["hd"]
    return d * hd * (m["nq"] + 2 * m["nkv"]) + m["nq"] * hd * d + mlp


def layer_params(cfg: Dict, kind: str) -> int:
    """Every weight of one layer: ``matmul_params``, the conv bias,
    ``dt_bias``, ``A_log``, ``D`` and the gated norm of a Mamba layer, and
    two RMSNorms."""
    m = dims(cfg)
    extra = m["cd"] + 3 * m["H"] + m["di"] if kind == "mamba" else 0
    return matmul_params(cfg, kind) + extra + 2 * m["d"]


def params(cfg: Dict) -> Dict[str, int]:
    """Parameter counts: ``layers`` (every layer and the final norm) and
    ``embed`` (the tied head)."""
    n = layer_counts(cfg)
    d = cfg["hidden_size"]
    return {"layers": sum(n[k] * layer_params(cfg, k) for k in n) + d,
            "embed": cfg["vocab_size"] * d}


def ssm_state_bytes(cfg: Dict, batch: int, dtype_bytes: int = BF16) -> int:
    """The recurrent state of ``batch`` sequences over every Mamba layer."""
    m = dims(cfg)
    return (layer_counts(cfg)["mamba"] * batch * m["H"] * m["P"] * m["N"]
            * dtype_bytes)


def conv_window_bytes(cfg: Dict, batch: int, dtype_bytes: int = BF16) -> int:
    """The conv windows (the last ``d_conv - 1`` inputs) of ``batch``
    sequences over every Mamba layer."""
    m = dims(cfg)
    return (layer_counts(cfg)["mamba"] * batch * (m["W"] - 1) * m["cd"]
            * dtype_bytes)


def kv_bytes_per_token(cfg: Dict, dtype_bytes: int = BF16) -> int:
    """K and V of one token over every attention layer."""
    m = dims(cfg)
    return (layer_counts(cfg)["attention"] * 2 * m["nkv"] * m["hd"]
            * dtype_bytes)


def ssm_update_bytes(cfg: Dict, batch: int) -> float:
    """One run of the fused state update (one layer, one decode step): each
    sequence's state read and written, its ``x``, ``dt``, ``B`` and ``C``
    read and its ``y`` written, and ``A`` and ``D`` read once."""
    m = dims(cfg)
    H, P, G, N = m["H"], m["P"], m["G"], m["N"]
    state = 2 * batch * H * P * N * BF16
    per_seq = (H * P + H + 2 * G * N + H * P) * F32
    return float(state + batch * per_seq + 2 * H * F32)


def decode_min_bytes(cfg: Dict, contexts: Iterable[int], slots: int,
                     versions: int, lanes: int) -> float:
    """The least a decode step must move: every weight once (the embedding
    only for the rows it looks up, then once as the head), each sequence's
    recurrent state and conv window read and written, its cached K/V read
    and the new token's written, and the descriptor store read once."""
    contexts = list(contexts)
    B = len(contexts)
    p = params(cfg)
    weights = (p["layers"] + p["embed"] + B * cfg["hidden_size"]) * BF16
    state = 2 * (ssm_state_bytes(cfg, B) + conv_window_bytes(cfg, B))
    kv = kv_bytes_per_token(cfg) * sum(contexts)
    return float(weights + state + kv
                 + descriptor_store_bytes(slots, versions, lanes))


def _ssd_flops(cfg: Dict, tokens: int) -> float:
    """The chunked SSD over one sequence of ``tokens``, one Mamba layer:
    per chunk of ``Q`` the causal ``C.B`` products and their use on ``x``
    (intra-chunk), the state read out at every position and the state
    update over the chunk (inter-chunk)."""
    m = dims(cfg)
    H, P, G, N = m["H"], m["P"], m["G"], m["N"]
    Q = cfg["mamba_chunk_size"]
    flops = 0.0
    for start in range(0, tokens, Q):
        q = min(Q, tokens - start)
        pairs = q * (q + 1) / 2
        flops += 2 * pairs * (G * N + H * P) + 4 * q * H * P * N
    return flops


def _attn_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """QK^T and PV of one query position per context entry, all attention
    layers."""
    m = dims(cfg)
    return (4.0 * layer_counts(cfg)["attention"] * m["nq"] * m["hd"]
            * float(sum(contexts)))


def _token_flops(cfg: Dict) -> float:
    n = layer_counts(cfg)
    return 2.0 * sum(n[k] * matmul_params(cfg, k) for k in n)


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """A prefill of ``batch`` prompts of ``prompt_len`` tokens that
    unembeds only the last position."""
    per_prompt = (_token_flops(cfg) * prompt_len
                  + _attn_flops(cfg, range(1, prompt_len + 1))
                  + layer_counts(cfg)["mamba"] * _ssd_flops(cfg, prompt_len)
                  + 2.0 * cfg["vocab_size"] * cfg["hidden_size"])
    return batch * per_prompt


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """One decode step: one new token per sequence, attending over
    ``contexts``; each Mamba layer's state updated and read out once."""
    contexts = list(contexts)
    m = dims(cfg)
    ssm = 4.0 * layer_counts(cfg)["mamba"] * m["H"] * m["P"] * m["N"]
    per_token = (_token_flops(cfg) + ssm
                 + 2.0 * cfg["vocab_size"] * cfg["hidden_size"])
    return len(contexts) * per_token + _attn_flops(cfg, contexts)
