"""Plain reference of the CLIP federated round.

Straightforward ``jax.numpy`` over float32 at ``highest`` matmul
precision, with no kernels, no caches, no batching over clients and no
code of the program: the weights are drawn again from the seed by the
same law as ``repro.core.clip.init_clip`` and
``repro.fl.client.init_trainable``, the backbone is NF4-quantized by the
published QLoRA codebook, and one round is each client's Adam steps one
after another, the int8 delta and the FedAvg sum.

``mode="fp8"`` is the control of the correctness check: every matrix
product rounds both operands to float8 e4m3 (a per-tensor absmax scale
onto the format's range), forward and backward, one step below the
bfloat16 operands the configuration states (float32 storage, the TPU's
default matmul precision).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# QLoRA's NF4 codebook (Dettmers et al. 2023, appendix E)
NF4 = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0], dtype=np.float32)

LORA_NAMES = ("wq", "wk", "wv", "wo")
FP8_MAX = 448.0
LORA_SCALE = 2.0           # alpha / r of the CLIP blocks
ADAPTER_HEADS = 4


# -- weights -------------------------------------------------------------

def _block(k, d, d_ff):
    ks = jax.random.split(k, 6)
    s = lambda f: 1.0 / jnp.sqrt(f)
    return {"ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
            "wq": jax.random.normal(ks[0], (d, d)) * s(d),
            "wk": jax.random.normal(ks[1], (d, d)) * s(d),
            "wv": jax.random.normal(ks[2], (d, d)) * s(d),
            "wo": jax.random.normal(ks[3], (d, d)) * s(d),
            "wu": jax.random.normal(ks[4], (d, d_ff)) * s(d),
            "wd": jax.random.normal(ks[5], (d_ff, d)) * s(d_ff)}


def init_clip(k, c: dict):
    """The dual encoder's weights, by ``init_clip``'s law; ``c`` is the
    configuration file's ``model`` group."""
    ks = jax.random.split(k, 10)
    d, p = c["d_model"], c["patch"]
    pdim = p * p * c["channels"]
    n_tok = (c["image_size"] // p) ** 2 + 1
    s = lambda f: 1.0 / jnp.sqrt(f)
    blocks = lambda kk, n: jax.vmap(lambda b: _block(b, d, c["d_ff"]))(
        jax.random.split(kk, n))
    return {
        "vision": {
            "patch_embed": jax.random.normal(ks[0], (pdim, d)) * s(pdim),
            "cls": jax.random.normal(ks[1], (d,)) * 0.02,
            "pos": jax.random.normal(ks[2], (n_tok, d)) * 0.02,
            "blocks": blocks(ks[3], c["vision_layers"]),
            "ln": jnp.zeros((d,))},
        "text": {
            "embed": jax.random.normal(ks[4], (c["vocab"], d)) * 0.02,
            "pos": jax.random.normal(ks[5], (c["max_text_len"], d)) * 0.02,
            "blocks": blocks(ks[6], c["text_layers"]),
            "ln": jnp.zeros((d,))},
        "proj_v": jax.random.normal(ks[7], (d, c["proj_dim"])) * s(d),
        "proj_t": jax.random.normal(ks[8], (d, c["proj_dim"])) * s(d),
        "logit_scale": jnp.asarray(jnp.log(1 / 0.07))}


def init_trainable(k, c: dict, *, use_lora: bool, rank: int):
    """Adapter (and vision LoRA) trainables, by ``init_trainable``'s
    law."""
    k1, k2 = jax.random.split(k)
    d = c["d_model"]
    ks = jax.random.split(k1, 6)
    s = 1.0 / jnp.sqrt(d)
    tr = {"adapter": {
        "wq": jax.random.normal(ks[0], (d, d)) * s,
        "wk": jax.random.normal(ks[1], (d, d)) * s,
        "wv": jax.random.normal(ks[2], (d, d)) * s,
        "wo": jnp.zeros((d, d)),
        "w1": jax.random.normal(ks[3], (d, d)) * s,
        "b1": jnp.zeros((d,)),
        "w2": jnp.zeros((d, d)),
        "b2": jnp.zeros((d,))}}
    if use_lora:
        layers = []
        for kl in jax.random.split(k2, c["vision_layers"]):
            layers.append({n: {
                "a": jax.random.normal(jax.random.fold_in(kl, i),
                                       (d, rank)) * (1 / np.sqrt(d)),
                "b": jnp.zeros((rank, d))}
                for i, n in enumerate(LORA_NAMES)})
        tr["lora"] = jax.tree.map(lambda *ls: jnp.stack(ls), *layers)
    return tr


def nf4_roundtrip(w, block: int):
    """Quantize ``w (..., K, N)`` to NF4 in blocks of ``block`` along K
    (absmax scaling, nearest code) and dequantize it again."""
    *lead, K, N = w.shape
    xb = w.astype(jnp.float32).reshape(*lead, K // block, block, N)
    absmax = jnp.maximum(jnp.max(jnp.abs(xb), axis=-2, keepdims=True),
                         1e-12)
    code = jnp.asarray(NF4)
    idx = jnp.argmin(jnp.abs((xb / absmax)[..., None] - code), axis=-1)
    return (code[idx] * absmax).reshape(w.shape)


def int_roundtrip(x, block: int, levels: int = 127):
    """Blockwise absmax integer quantization along axis -2 (the uplink's
    int8 layout) and back."""
    *lead, K, N = x.shape
    xb = x.reshape(*lead, K // block, block, N)
    absmax = jnp.maximum(jnp.max(jnp.abs(xb), axis=-2, keepdims=True),
                         1e-12)
    scale = absmax / levels
    q = jnp.clip(jnp.round(xb / scale), -levels, levels)
    return (q * scale).reshape(x.shape)


def pick_block(K: int, block: int) -> int:
    b = min(block, K)
    while K % b:
        b //= 2
    return max(b, 1)


def quantize_backbone(frozen, block: int):
    """The vision tower's block matrices through NF4 and back (norms,
    embeddings and positions stay float, as QLoRA keeps them)."""
    blocks = dict(frozen["vision"]["blocks"])
    for n in ("wq", "wk", "wv", "wo", "wu", "wd"):
        blocks[n] = nf4_roundtrip(blocks[n], block)
    return {**frozen, "vision": {**frozen["vision"], "blocks": blocks}}


# -- forward -------------------------------------------------------------

def _round8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


@jax.custom_vjp
def fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor absmax scale; in
    the backward pass the cotangent is rounded the same way, under its
    own scale."""
    return _round8(x)


fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def rounding(mode: str):
    """The operand rounding of every product for a reference mode."""
    if mode == "fp8":
        return fp8
    if mode == "f32":
        return lambda x: x
    raise ValueError(f"unknown reference mode {mode!r}")


def _ln(x, w, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1 + w)


def _id(x):
    return x


def _attend(q, k, v, causal, r=_id):
    """q, k, v: (B, S, H, dh)."""
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s,
                      jnp.asarray(-1e30, s.dtype))
    return jnp.einsum("bhqk,bkhd->bqhd", r(jax.nn.softmax(s, -1)), r(v))


def block(p, x, n_heads, causal, lora=None, r=_id):
    B, S, d = x.shape
    dh = d // n_heads

    def lin(name, h):
        y = r(h) @ r(p[name])
        if lora is not None and name in lora:
            y = y + LORA_SCALE * (r(r(h) @ r(lora[name]["a"])) @
                                  r(lora[name]["b"]))
        return y

    h = _ln(x, p["ln1"])
    q, k, v = (lin(n, h).reshape(B, S, n_heads, dh)
               for n in ("wq", "wk", "wv"))
    x = x + lin("wo", _attend(q, k, v, causal, r).reshape(B, S, d))
    h = _ln(x, p["ln2"])
    return x + r(jax.nn.gelu(r(h) @ r(p["wu"]))) @ r(p["wd"])


def run_blocks(blocks, x, n_heads, causal, lora=None, r=_id):
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        x = block(jax.tree.map(lambda l: l[i], blocks), x, n_heads, causal,
                  None if lora is None else
                  jax.tree.map(lambda l: l[i], lora), r)
    return x


def embed_patches(frozen, c, images, r=_id):
    v = frozen["vision"]
    B, H, W, C = images.shape
    p = c["patch"]
    g = H // p
    x = images.reshape(B, g, p, g, p, C).transpose(0, 1, 3, 2, 4, 5)
    x = r(x.reshape(B, g * g, p * p * C)) @ r(v["patch_embed"])
    cls = jnp.broadcast_to(v["cls"], (B, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + v["pos"][None]


def encode_tokens(frozen, c, x, lora=None, r=_id):
    v = frozen["vision"]
    x = run_blocks(v["blocks"], x, c["n_heads"], False, lora, r)
    return _ln(x, v["ln"])[:, 0]


def class_embedding(frozen, c, tokens):
    t = frozen["text"]
    x = t["embed"][tokens] + t["pos"][None, :tokens.shape[1]]
    x = _ln(run_blocks(t["blocks"], x, c["n_heads"], True), t["ln"])
    return x[:, -1] @ frozen["proj_t"]


def adapter(a, x, r=_id):
    """The attention adapter on pooled features ``x (B, d)`` as a
    length-one sequence."""
    B, d = x.shape
    dh = d // ADAPTER_HEADS
    x = x[:, None, :]
    q, k, v = ((r(x) @ r(a[n])).reshape(B, 1, ADAPTER_HEADS, dh)
               for n in ("wq", "wk", "wv"))
    x = x + r(_attend(q, k, v, False, r).reshape(B, 1, d)) @ r(a["wo"])
    h = jax.nn.relu(r(x) @ r(a["w1"]) + a["b1"])
    return (x + r(h) @ r(a["w2"]) + a["b2"])[:, 0]


def head_logits(frozen, tr, feat, class_emb, r=_id):
    emb = r(adapter(tr["adapter"], feat, r)) @ r(frozen["proj_v"])
    ie = emb / (jnp.linalg.norm(emb, axis=-1, keepdims=True) + 1e-8)
    te = class_emb / (jnp.linalg.norm(class_emb, axis=-1, keepdims=True)
                      + 1e-8)
    return jnp.exp(frozen["logit_scale"]) * r(ie) @ r(te).T


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


# -- Adam ----------------------------------------------------------------

def adam_step(g, state, p, *, lr, clip=1.0, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step after clipping the gradient's global norm to
    ``clip``; returns (params, state, the clipped gradient)."""
    n, m, v = state
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g)))
    g = jax.tree.map(lambda l: l * jnp.minimum(1.0, clip / (gn + 1e-9)), g)
    n = n + 1
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    c1 = 1.0 / (1 - b1 ** n.astype(jnp.float32))
    c2 = 1.0 / (1 - b2 ** n.astype(jnp.float32))
    p = jax.tree.map(lambda w, a, b: w - lr * (a * c1) / (
        jnp.sqrt(b * c2) + eps), p, m, v)
    return p, (n, m, v), g


def adam_init(p):
    z = jax.tree.map(jnp.zeros_like, p)
    return (jnp.zeros((), jnp.int32), z, jax.tree.map(jnp.zeros_like, z))


# -- one federated round ----------------------------------------------

def batch_indices(k, lens, steps: int, batch: int):
    """(clients, steps, batch) indices, client i's in [0, lens[i]): the
    per-round draw, one key per client."""
    keys = jax.random.split(k, lens.shape[0])
    return jax.vmap(lambda kk, n: jax.random.randint(
        kk, (steps, batch), 0, n))(keys, lens)


def make_local_train(c: dict, *, use_lora: bool, lr: float,
                     mode: str = "f32", rows: Optional[int] = None):
    """A jitted ``(frozen, class_emb, tr, xs, ys) -> (tr, losses,
    per-step grad norms by leaf)`` over one client's batches
    ``xs (steps, batch, ...)``. ``rows`` keeps only the first rows of each
    batch (a planted fault of the correctness check)."""
    r = rounding(mode)

    def loss_fn(tr, frozen, class_emb, x, y):
        feat = encode_tokens(frozen, c, x, tr.get("lora"), r) if use_lora \
            else x
        return cross_entropy(head_logits(frozen, tr, feat, class_emb, r), y)

    @jax.jit
    def local(frozen, class_emb, tr, xs, ys):
        if rows is not None:
            xs, ys = xs[:, :rows], ys[:, :rows]

        def step(carry, xy):
            p, st = carry
            loss, g = jax.value_and_grad(loss_fn)(p, frozen, class_emb,
                                                  *xy)
            p, st, gc = adam_step(g, st, p, lr=lr)
            norms = jax.tree.map(lambda l: jnp.sqrt(jnp.sum(l * l)), gc)
            return (p, st), (loss, norms)

        (tr, _), (losses, norms) = jax.lax.scan(
            step, (tr, adam_init(tr)), (xs, ys))
        return tr, losses, norms

    return local


def uplink(delta, *, comm_bits: int, comm_block: int, min_size: int):
    """Each eligible delta leaf (>= 2-D, at least ``min_size`` values)
    through the blockwise int8 uplink and back."""
    if not comm_bits:
        return delta

    def f(x):
        if x.ndim < 2 or x.size < min_size:
            return x
        return int_roundtrip(x, pick_block(x.shape[-2], comm_block))
    return jax.tree.map(f, delta)
