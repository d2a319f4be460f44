"""Operations and bytes of a Jamba train step and of its new kernels,
computed from shapes, by ``bench/lib/flops.py``'s rule: the forward, the
input gradient (``dx``) of every matrix product, the weight gradient only
of trainable weights (LoRA, the adapter), nothing a rematerialized
forward recomputes. Matrix products only: the causal conv and the
selective scan are element-wise and not counted. The experts count only
the routed rows (``T·k``), never the rows that pad each expert's group
to whole row tiles, so padding shows as lost roofline.

The selective scan's kernels do element-wise VPU work, for which
``bench/lib/peaks.py`` has no peak; their least time is their bytes over
the HBM bandwidth (819 GB/s), which makes their roofline a share of the
memory bound.
"""
from __future__ import annotations

from bench.lib.flops import (BF16, F32, attention_fwd, lora_fwd,
                             lora_matmul_call, mm, nf4_bytes,
                             quant_matmul_t_call)


def _widths(m: dict):
    d = m["d_model"]
    return (d, m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"],
            m["ssm_expand"] * d, m["d_ff"], m["lora_rank"])


def step(m: dict, kinds, batch: int, seq: int) -> float:
    """Operations of one ``Model.train_step``; ``kinds`` is each layer's
    (mixer, feed-forward), ``ref_jamba.sizes(config)["kinds"]`` over the
    layers."""
    d, qd, kvd, di, ff, r = _widths(m)
    N, R, E, k = (m["ssm_state"], m["ssm_dt_rank"], m["n_experts"],
                  m["experts_per_token"])
    T, V = batch * seq, m["vocab_size"]

    def lora(shapes):          # frozen fwd + dx, LoRA fwd + dx + dA/dB
        return sum(2 * mm(T, a, b) + 3 * lora_fwd(T, a, b, r)
                   for a, b in shapes)

    total = 0.0
    for mixer, ffn in kinds:
        if mixer == "attn":
            total += lora([(d, qd), (d, kvd), (d, kvd), (qd, d)])
            total += 3 * attention_fwd(batch, seq, seq, qd, True)
        else:
            total += lora([(d, di), (di, d)])
            total += 2 * (mm(T, d, di) + mm(T, di, R + 2 * N) +
                          mm(T, R, di))
        if ffn == "moe":
            total += 2 * (mm(T, d, E) + 2 * mm(T * k, d, ff) +
                          mm(T * k, ff, d))
        else:
            total += lora([(d, ff), (d, ff), (ff, d)])
    ad_d = m.get("adapter_d_ff") or d
    adapter = 4 * mm(T, d, d) + mm(T, d, ad_d) + mm(T, ad_d, d)
    total += 3 * adapter + 3 * attention_fwd(batch, seq, seq, d, True)
    return total + 2 * mm(T, d, V)


def lora_calls(m: dict, kinds, batch: int, seq: int, x_bytes: int = BF16):
    """The LoRA'd frozen projections that run through the fused NF4
    kernels, one entry per call in a period: attention's ``wq``, ``wk``,
    ``wv`` and ``wo``, and each dense FFN's ``wg``, ``wu`` and ``wd``
    (Mamba's projections are dequantized and multiplied in XLA). Returns
    the forward kernel's calls and the backward dx kernel's."""
    d, qd, kvd, _, ff, r = _widths(m)
    T, blk = batch * seq, m["quant_block"]
    shapes = []
    for mixer, ffn in kinds:
        if mixer == "attn":
            shapes += [(d, qd), (d, kvd), (d, kvd), (qd, d)]
        if ffn == "mlp":
            shapes += [(d, ff), (d, ff), (ff, d)]
    fwd = [lora_matmul_call(T, k, n, r, x_bytes=x_bytes, block=blk)
           for k, n in shapes]
    bwd = [quant_matmul_t_call(T, k, n, block=blk) for k, n in shapes]
    return fwd, bwd


def gmm_calls(m: dict, batch: int, seq: int, x_bytes: int = BF16):
    """The expert layer's grouped matmuls as kernel calls: the forward's
    gate, up and down products, and their input-gradient twins. Each
    reads the routed rows and every expert's NF4 weight once and writes
    the routed rows."""
    d, ff, E = m["d_model"], m["d_ff"], m["n_experts"]
    Tk, blk = batch * seq * m["experts_per_token"], m["quant_block"]

    def call(K, N):
        return {"flops": mm(Tk, K, N),
                "bytes": Tk * K * x_bytes + E * nf4_bytes(K, N, blk) +
                Tk * N * x_bytes}

    fwd = [call(d, ff), call(d, ff), call(ff, d)]
    dx = [call(d, ff), call(d, ff), call(ff, d)]   # g@Wdᵀ, g@Wgᵀ, g@Wuᵀ
    return fwd, dx


def scan_calls(m: dict, batch: int, seq: int, chunk: int = 128):
    """The selective scan's forward and backward kernels as calls with no
    operations: the forward reads Δ, x, B, C and A and writes y, the
    final state and the state entering each chunk; the backward reads
    those inputs, the chunk states and y's cotangent, and writes the
    cotangents of Δ, x, B, C and A."""
    d, di, N = m["d_model"], m["ssm_expand"] * m["d_model"], m["ssm_state"]
    T = batch * seq
    chunks = batch * -(-seq // chunk)
    ins = 2 * T * di * F32 + 2 * T * N * F32 + di * N * F32
    states = chunks * N * di * F32
    fwd = {"flops": 0.0,
           "bytes": ins + T * di * F32 + batch * di * N * F32 + states}
    bwd = {"flops": 0.0,
           "bytes": ins + states + T * di * F32 + ins}
    return [fwd], [bwd]
