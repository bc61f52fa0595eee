"""Operations and HBM bytes of each kernel call, and of whole forwards and
serving steps, computed from the logical shapes the caller passed.

Rows are never the kernel's padded blocks: a kernel that pads M to its
block size does the padded work at its own cost, and that shows as a lower
roofline share. A multiply-add counts as two operations.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def matmul(m: int, d: int, n: int, in_bytes: int, out_bytes: int = 4
           ) -> Tuple[float, float]:
    """(ops, bytes) of a CADC matmul x[m, d] @ w[d, n] -> y[m, n]: every
    operand read once and the output written once."""
    return 2.0 * m * d * n, \
        float((m * d + d * n) * in_bytes + m * n * out_bytes)


def conv_out_hw(h: int, stride: int) -> int:
    """Output size of a SAME-padded conv."""
    return -(-h // stride)


def conv2d(b: int, h: int, cin: int, cout: int, k: int, stride: int,
           in_bytes: int, out_bytes: int = 4) -> Tuple[float, float]:
    """(ops, bytes) of a SAME-padded k x k conv on [b, h, h, cin]: the
    input map, the weights and the output each cross HBM once (the fused
    kernel never writes patches or psums)."""
    oh = conv_out_hw(h, stride)
    ops = 2.0 * b * oh * oh * k * k * cin * cout
    byt = (b * h * h * cin + k * k * cin * cout) * in_bytes \
        + b * oh * oh * cout * out_bytes
    return ops, float(byt)


# ---------------------------------------------------------------------------
# ResNet-18 (CIFAR variant)
# ---------------------------------------------------------------------------

def resnet18_convs(cfg: Dict) -> List[Tuple[int, int, int, int, int]]:
    """(h_in, cin, cout, k, stride) of every conv of one forward, in order:
    stem, then per block conv1, conv2 and the 1x1 projection where the
    block changes shape."""
    w, h = cfg["width"], cfg["image_hw"]
    convs = [(h, cfg["in_ch"], w, 3, 1)]
    cin = w
    for si, n_blocks in enumerate(cfg["stages"]):
        cout = w * 2 ** si
        for bi in range(n_blocks):
            s = 2 if (si > 0 and bi == 0) else 1
            convs.append((h, cin, cout, 3, s))
            h_out = conv_out_hw(h, s)
            convs.append((h_out, cout, cout, 3, 1))
            if s != 1 or cin != cout:
                convs.append((h, cin, cout, 1, s))
            cin, h = cout, h_out
    return convs


def resnet18_fc(cfg: Dict) -> Tuple[int, int]:
    return cfg["width"] * 2 ** (len(cfg["stages"]) - 1), cfg["num_classes"]


def resnet18_macs_per_image(cfg: Dict) -> float:
    macs = sum(conv2d(1, *c, in_bytes=4)[0] for c in resnet18_convs(cfg)) / 2
    d, n = resnet18_fc(cfg)
    return macs + d * n


def resnet18_conv_calls(cfg: Dict, batch: int, in_bytes: int
                        ) -> List[Tuple[float, float]]:
    """(ops, bytes) of each conv kernel call of one forward of `batch`."""
    return [conv2d(batch, *c, in_bytes=in_bytes) for c in resnet18_convs(cfg)]


# ---------------------------------------------------------------------------
# decoder LM (phi4-style: GQA attention + SwiGLU, tied embeddings)
# ---------------------------------------------------------------------------

def decoder_linears(cfg: Dict) -> List[Tuple[int, int]]:
    """(d_in, d_out) of the CADC linears of one layer: q, k, v, o, gate,
    up, down."""
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    return [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]


def decoder_layer_params(cfg: Dict) -> int:
    """Weights of one layer: its linears and its two norm scales."""
    return sum(a * b for a, b in decoder_linears(cfg)) + 2 * cfg["hidden_size"]


def decoder_embed_params(cfg: Dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def decoder_kernel_calls(cfg: Dict, rows: int) -> List[Tuple[float, float]]:
    """(ops, bytes) of each CADC matmul call of one program that runs
    `rows` rows through all layers (bf16 operands, fp32 output)."""
    one = [matmul(rows, d_in, d_out, in_bytes=2)
           for d_in, d_out in decoder_linears(cfg)]
    return one * cfg["num_hidden_layers"]


def decoder_model_ops(cfg: Dict, new_tokens: int, context_before: int,
                      logits_rows: int) -> float:
    """Operations a request needs to process `new_tokens` tokens that
    follow `context_before` cached ones: every linear of every layer,
    causal attention (QK^T and AV) over the tokens it may see, and the
    tied LM head on `logits_rows` rows (1 for a prefill, which needs only
    the last position's logits)."""
    lin = sum(a * b for a, b in decoder_linears(cfg))
    # sum over the new tokens of the number of keys each attends
    keys = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2
    attn = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * keys
    per_layer = 2.0 * lin * new_tokens + attn
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_rows
    return per_layer * cfg["num_hidden_layers"] + head
