"""Operations and bytes the algorithm needs, computed from shapes.

Training counts follow one rule: the forward pass, and in the backward
pass the input gradient of every matrix product (``dx``) plus the weight
gradient (``dW``) only of trainable weights; a frozen weight needs no
``dW``, LoRA factors need ``dA`` and ``dB``, and work that a
rematerialized forward recomputes is not counted. A matrix product of
(M, K) by (K, N) is 2MKN operations.
"""
from __future__ import annotations

import math

F32, BF16 = 4, 2


def mm(m, k, n) -> float:
    return 2.0 * m * k * n


def lora_fwd(m, k, n, r) -> float:
    """x@A then (x@A)@B."""
    return mm(m, k, r) + mm(m, r, n)


def attention_fwd(b, s_q, s_k, d, causal) -> float:
    """QK^T and PV over ``b`` sequences; a causal mask needs only the
    pairs at or below the diagonal."""
    pairs = s_q * (s_q + 1) / 2 if causal else s_q * s_k
    return 2 * 2.0 * b * pairs * d


def clip_round(model: dict, traffic: dict, *, use_lora: bool,
               rank: int) -> float:
    """Operations of one federated round of the CLIP cohort engine: each
    client's local steps through the frozen vision tower (LoRA arms) or
    the adapter head alone (the backbone is hoisted into staging), for
    ``clients x local_steps x batch`` samples."""
    d, f = model["d_model"], model["d_ff"]
    L = model["vision_layers"]
    S = (model["image_size"] // model["patch"]) ** 2 + 1
    P, C = model["proj_dim"], traffic["n_classes"]
    per = 0.0
    if use_lora:
        frozen = 4 * mm(S, d, d) + mm(S, d, f) + mm(S, f, d)
        att = attention_fwd(1, S, S, d, False)
        lo = 4 * lora_fwd(S, d, d, rank)
        # forward; backward dx of frozen products; attention 2x; LoRA 2x
        per += L * (frozen + att + lo + frozen + 2 * att + 2 * lo)
    adapter = 6 * mm(1, d, d)               # wq wk wv wo w1 w2
    head = mm(1, d, P) + mm(1, P, C)
    per += 3 * adapter + 2 * head           # adapter dx+dW; head dx
    n = traffic["n_clients"] * traffic["local_steps"] * traffic["batch"]
    return n * per


def trunk_step(m: dict, batch: int, seq: int) -> float:
    """Operations of one ``Model.train_step`` of a dense trunk with LoRA
    on every projection (frozen backbone, LoRA, adapter and the frozen
    output head), counted by the rule above."""
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    L, r = m["n_layers"], m["lora_rank"]
    T = batch * seq
    proj = [(d, d)] * 4 + [(d, f), (f, d)]
    frozen = sum(mm(T, k, n) for k, n in proj)
    lo = sum(lora_fwd(T, k, n, r) for k, n in proj)
    att = attention_fwd(batch, seq, seq, d, True)
    layers = L * (2 * frozen + 3 * att + 3 * lo)
    ad_d = m.get("adapter_d_ff") or d
    adapter = 4 * mm(T, d, d) + mm(T, d, ad_d) + mm(T, ad_d, d)
    adapter = 3 * adapter + 3 * att          # trainable: fwd, dx, dW
    head = 2 * mm(T, d, V)                    # frozen: fwd and dx
    return layers + adapter + head


# -- kernels: operations, bytes and the least time on a chip -----------

def nf4_bytes(k, n, block) -> float:
    return k * n / 2 + (k / block) * n * F32


def lora_matmul_call(m, k, n, r, *, x_bytes, block) -> dict:
    """``x (M,K) @ dequant(W (K,N)) + s (x@A)@B``: the fused forward."""
    return {"flops": mm(m, k, n) + lora_fwd(m, k, n, r),
            "bytes": m * k * x_bytes + nf4_bytes(k, n, block) +
            k * r * F32 + r * n * F32 + m * n * x_bytes}


def quant_matmul_t_call(m, k, n, *, block) -> dict:
    """``g (M,N) @ dequant(W (K,N))^T``: the backward's dx product, on
    float32 cotangents."""
    return {"flops": mm(m, k, n),
            "bytes": m * n * F32 + nf4_bytes(k, n, block) + m * k * F32}


def least_time(call: dict, pk: dict) -> float:
    return max(call["flops"] / pk["bf16_flops"],
               call["bytes"] / pk["hbm_bytes_per_s"])


def bound(call: dict, pk: dict) -> str:
    ridge = pk["bf16_flops"] / pk["hbm_bytes_per_s"]
    return "compute" if call["flops"] / call["bytes"] >= ridge else "memory"


def trunk_kernel_calls(m: dict, batch: int, seq: int, x_bytes: int):
    """The projections of one trunk layer as kernel calls, for the
    forward kernel and the backward dx kernel."""
    d, f, r = m["d_model"], m["d_ff"], m["lora_rank"]
    T, blk = batch * seq, m["quant_block"]
    proj = [(d, d)] * 4 + [(d, f), (f, d)]
    fwd = [lora_matmul_call(T, k, n, r, x_bytes=x_bytes, block=blk)
           for k, n in proj]
    bwd = [quant_matmul_t_call(T, k, n, block=blk) for k, n in proj]
    return fwd, bwd


def mean_least_time(calls, pk) -> float:
    return math.fsum(least_time(c, pk) for c in calls) / len(calls)
