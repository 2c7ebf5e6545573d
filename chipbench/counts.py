"""Operations and bytes that each measured step or kernel needs, from shapes.

These are the algorithm's counts, not the compiler's: a program that moves
or computes more than this is charged for it in its roofline share.  Every
function takes the configuration dict as ``chipbench/configs/*.json`` holds
it (keys of the source's ``config.json``) and plain sizes.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2
I32 = 4


def head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_params(cfg: Dict) -> int:
    """Weights of one decoder layer: attention, the MLP, biases, two norms."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * hd * (nq + 2 * nkv) + nq * hd * d
    # SwiGLU (silu) has gate, up and down matrices; a plain GELU MLP two
    mats = 3 if cfg.get("hidden_act") == "silu" else 2
    bias = (hd * (nq + 2 * nkv) + d + f + d) if cfg.get("use_bias") else 0
    return attn + mats * d * f + bias + 2 * d


def params(cfg: Dict) -> Dict[str, int]:
    """Parameter counts: ``layers`` (all decoder layers and the final norm),
    ``embed`` and ``unembed`` (the output head; 0 when tied)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "layers": cfg["num_hidden_layers"] * layer_params(cfg) + d,
        "embed": v * d,
        "unembed": 0 if cfg.get("tie_word_embeddings", True) else v * d,
    }


def kv_bytes_per_token(cfg: Dict, dtype_bytes: int = BF16) -> int:
    """K and V of one token over every layer."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * dtype_bytes)


def _attn_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """QK^T and PV of one query position per context entry, all layers."""
    nq, hd = cfg["num_attention_heads"], head_dim(cfg)
    return 4.0 * cfg["num_hidden_layers"] * nq * hd * float(sum(contexts))


def prefill_flops(cfg: Dict, batch: int, prompt_len: int) -> float:
    """A causal prefill of ``batch`` prompts of ``prompt_len`` tokens that
    unembeds only the last position (all it needs for the first token)."""
    p = params(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    matmul = 2.0 * (p["layers"] - 2 * d * cfg["num_hidden_layers"] - d)
    per_prompt = (matmul * prompt_len
                  + _attn_flops(cfg, range(1, prompt_len + 1))
                  + 2.0 * v * d)
    return batch * per_prompt


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """One decode step: one new token per sequence, attending over
    ``contexts`` (each sequence's length including the new token)."""
    contexts = list(contexts)
    p = params(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    matmul = 2.0 * (p["layers"] - 2 * d * cfg["num_hidden_layers"] - d)
    return len(contexts) * (matmul + 2.0 * v * d) + _attn_flops(cfg, contexts)


def descriptor_store_bytes(slots: int, versions: int, lanes: int) -> int:
    """The version slabs (timestamp, successor, payload) and the pin board."""
    return 3 * slots * versions * I32 + lanes * I32


def decode_min_bytes(cfg: Dict, contexts: Iterable[int], slots: int,
                     versions: int, lanes: int,
                     weight_bytes: int = BF16, kv_dtype_bytes: int = BF16
                     ) -> float:
    """The least a decode step must move: every weight once (the embedding
    only for the rows it looks up), each sequence's cached K/V read and the
    new token's K/V written, and the descriptor store read once."""
    contexts = list(contexts)
    p = params(cfg)
    head = p["unembed"] or p["embed"]
    rows = len(contexts) * cfg["hidden_size"]
    weights = (p["layers"] + head + rows) * weight_bytes
    # each sequence reads its c - 1 cached tokens and writes the new one
    kv = kv_bytes_per_token(cfg, kv_dtype_bytes) * sum(contexts)
    return float(weights + kv
                 + descriptor_store_bytes(slots, versions, lanes))


def compact_bytes(rows: int, versions: int) -> float:
    """Fused needed-and-splice over ``rows`` version slabs: timestamps,
    successors and payloads read and written back, the row mask read, the
    freed handles written."""
    slab = rows * versions * I32
    return float(3 * slab + rows * I32 + 3 * slab + slab)


def search_gather_bytes(queries: int, versions: int, row_words: int) -> float:
    """Snapshot search of ``queries`` slots and the gather of each hit's
    value row: each slot's timestamps and payloads read, one value row read
    and written per query, plus the payload and found columns."""
    return float(2 * queries * versions * I32
                 + 2 * queries * row_words * I32
                 + 2 * queries * I32)
