"""Operations and HBM bytes of the lfm2 cell's grouped expert kernel and
of its model's steps, from logical shapes and the rows the program counted
routed to each held expert (`flops.py` has the other cells'; the same
conventions: a multiply-add is two operations, rows are never the
kernel's padded tiles)."""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


def expert_linears(cfg: Dict) -> List[Tuple[int, int]]:
    """(d_in, d_out) of an expert's gate, up and down linears."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [(d, f), (d, f), (f, d)]


def gmm_call(rows: Sequence[int], d_in: int, d_out: int, in_bytes: int = 2
             ) -> Tuple[float, float]:
    """(ops, bytes) of one grouped CADC matmul call: `rows` [E_here] live
    rows per held expert; the rows cross HBM in and out once. The weights
    are not counted: the compiler's memory-space assignment may place the
    kernel's weight operand in VMEM (the bfloat16 cast before the call
    writes it there, memory space S(1) in the program compiled for a
    v5e), and their read from HBM is then the cast's, not the kernel's.
    Counted, the decode calls on a TPU v5e ran at 2.2x the HBM bandwidth
    (6.65 experts' weights, 18 calls, in 0.478 ms)."""
    live = sum(rows)
    ops = 2.0 * live * d_in * d_out
    return ops, float(live * (d_in + d_out) * in_bytes)


def gmm_calls(cfg: Dict, routed: Sequence[Sequence[int]]
              ) -> List[Tuple[float, float]]:
    """(ops, bytes) of each kernel call of one program, from its counter:
    `routed` [n_moe_layers][E_here]."""
    return [gmm_call(rows, a, b) for rows in routed
            for a, b in expert_linears(cfg)]


def layer_kinds(cfg: Dict) -> List[str]:
    return cfg["layer_types"][: cfg["num_hidden_layers"]]


def dense_ops_per_token(cfg: Dict) -> float:
    """Operations a token needs outside the experts and attention's
    products: every mixer's linears, the conv, the dense FFNs and the
    routers."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, ff = d // h, cfg["intermediate_size"]
    macs = 0
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "conv":
            macs += d * 3 * d + d * d + cfg["conv_L_cache"] * d
        else:
            macs += d * h * hd + 2 * d * kv * hd + h * hd * d
        if i < cfg["num_dense_layers"]:
            macs += 3 * d * ff
        else:
            macs += d * cfg["published"]["num_experts"]
    return 2.0 * macs


def attention_ops(cfg: Dict, new_tokens: int, context_before: int) -> float:
    """QK^T and AV over the keys each new token may see, every attention
    layer."""
    n_attn = sum(1 for k in layer_kinds(cfg) if k != "conv")
    keys = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2
    return 2 * 2 * cfg["hidden_size"] * keys * n_attn


def expert_ops(cfg: Dict, routed: Sequence[Sequence[int]]) -> float:
    """Operations of the held experts on the rows routed here."""
    return sum(o for o, _ in gmm_calls(cfg, routed))


def model_ops(cfg: Dict, new_tokens: int, context_before: int,
              logits_rows: int) -> float:
    """Operations outside the experts for `new_tokens` tokens after
    `context_before` cached ones, with the tied head on `logits_rows`."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_rows
    return (dense_ops_per_token(cfg) * new_tokens
            + attention_ops(cfg, new_tokens, context_before) + head)

