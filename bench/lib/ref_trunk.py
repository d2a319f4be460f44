"""Plain reference of one local training step of the dense ``models/``
trunk (the CLIP text-transformer widths) with an NF4 backbone, LoRA on
every projection, the attention adapter and the frozen output head.

Float32 at ``highest`` matmul precision, no kernels and no code of
the program. The weights are drawn again from the seed by the law of
``repro.models.Model.init_params`` (in the configuration's dtype) and
the backbone goes through the published NF4 codebook.

``mode="fp8"`` is the control: every matrix product rounds both operands
to float8 e4m3 (a per-tensor absmax scale onto the format's range), the
step below the bfloat16 the configuration states. Each layer is
rematerialized in the backward pass (``jax.checkpoint``), which changes
no value and keeps the control's float8 copies inside the chip.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bench.lib import ref_clip


def _normal(k, shape, dt):
    return jax.random.normal(k, shape, dt)


def init(k, m: dict):
    """(frozen with dense float32 weights after NF4, trainable) by the
    trunk's init law; ``m`` is the configuration file's ``model``
    group."""
    dt = jnp.dtype(m["dtype"])
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    qd = m["n_heads"] * m["head_dim"]
    kvd = m["n_kv_heads"] * m["head_dim"]
    r = m["lora_rank"]
    k_emb, k_head, k_lay, _, _, k_lora, k_ad, _ = jax.random.split(k, 8)
    s = lambda fan_in: 1.0 / jnp.sqrt(fan_in)

    def layer(kk):
        ks = jax.random.split(kk, 8)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 3)
        return {"ln1": jnp.zeros((d,), jnp.float32),
                "wq": _normal(ka[0], (d, qd), dt) * s(d),
                "wk": _normal(ka[1], (d, kvd), dt) * s(d),
                "wv": _normal(ka[2], (d, kvd), dt) * s(d),
                "wo": _normal(ka[3], (qd, d), dt) * s(qd),
                "ln2": jnp.zeros((d,), jnp.float32),
                "wu": _normal(km[0], (d, f), dt) * s(d),
                "wd": _normal(km[1], (f, d), dt) * s(f)}

    layers = jax.vmap(layer)(jax.random.split(k_lay, m["n_layers"]))
    for n in ("wq", "wk", "wv", "wo", "wu", "wd"):
        layers[n] = ref_clip.nf4_roundtrip(layers[n], m["quant_block"])
    frozen = {
        "embed": (_normal(k_emb, (V, d), dt) * 0.02).astype(jnp.float32),
        "head": (_normal(k_head, (d, V), dt) /
                 jnp.sqrt(jnp.asarray(d, dt))).astype(jnp.float32),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "layers": layers}

    shapes = {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd),
              "wo": (qd, d), "wu": (d, f), "wd": (f, d)}

    def lora_layer(kk):
        ks = jax.random.split(kk, len(shapes))
        return {n: {"a": _normal(kx, (a, r), jnp.float32) *
                    (1.0 / jnp.sqrt(a)),
                    "b": jnp.zeros((r, b), jnp.float32)}
                for (n, (a, b)), kx in zip(sorted(shapes.items()), ks)}

    ka = jax.random.split(k_ad, 6)
    sd = 1.0 / jnp.sqrt(d)
    ad_f = m.get("adapter_d_ff") or d
    trainable = {
        "lora": jax.vmap(lora_layer)(jax.random.split(k_lora,
                                                      m["n_layers"])),
        "adapter": {
            "wq": _normal(ka[0], (d, d), jnp.float32) * sd,
            "wk": _normal(ka[1], (d, d), jnp.float32) * sd,
            "wv": _normal(ka[2], (d, d), jnp.float32) * sd,
            "wo": jnp.zeros((d, d), jnp.float32),
            "w1": _normal(ka[3], (d, ad_f), jnp.float32) * sd,
            "b1": jnp.zeros((ad_f,), jnp.float32),
            "w2": jnp.zeros((ad_f, d), jnp.float32),
            "b2": jnp.zeros((d,), jnp.float32)}}
    return frozen, trainable


def _mm(mode):
    r = ref_clip.rounding(mode)
    return lambda a, b: r(a) @ r(b)


def rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, theta):
    B, S, H, D = x.shape
    half = D // 2
    freq = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, mm):
    """(B, S, H, D) each; softmax(QK^T / sqrt(D)) V under a causal
    mask."""
    B, S, H, D = q.shape
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    s = mm(qh, kh.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o = mm(jax.nn.softmax(s, -1), vh)
    return o.transpose(0, 2, 1, 3)


def loss_fn(trainable, frozen, tokens, labels, m: dict, mode: str = "f32"):
    mm = _mm(mode)
    H, D = m["n_heads"], m["head_dim"]
    scale = m["lora_alpha"] / m["lora_rank"]
    x = frozen["embed"][tokens]
    B, S, d = x.shape

    def lin(h, w, lo):
        return mm(h, w) + scale * mm(mm(h, lo["a"]), lo["b"])

    def layer(x, pl):
        p, lo = pl
        h = rms_norm(x, p["ln1"])
        q = rope(lin(h, p["wq"], lo["wq"]).reshape(B, S, H, D),
                 m["rope_theta"])
        k = rope(lin(h, p["wk"], lo["wk"]).reshape(B, S, H, D),
                 m["rope_theta"])
        v = lin(h, p["wv"], lo["wv"]).reshape(B, S, H, D)
        o = causal_attention(q, k, v, mm).reshape(B, S, H * D)
        x = x + lin(o, p["wo"], lo["wo"])
        h = rms_norm(x, p["ln2"])
        return x + lin(jax.nn.gelu(lin(h, p["wu"], lo["wu"])), p["wd"],
                       lo["wd"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                        (frozen["layers"], trainable["lora"]))
    x = rms_norm(x, frozen["final_norm"])
    a = trainable["adapter"]
    Ha = m["adapter_heads"]
    q, k, v = (mm(x, a[n]).reshape(B, S, Ha, d // Ha)
               for n in ("wq", "wk", "wv"))
    x = x + mm(causal_attention(q, k, v, mm).reshape(B, S, d), a["wo"])
    h = jax.nn.relu(mm(x, a["w1"]) + a["b1"])
    x = x + mm(h, a["w2"]) + a["b2"]
    logits = mm(x, frozen["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def make_step(m: dict, *, lr: float, mode: str = "f32",
              rows: Optional[int] = None):
    """A jitted ``(trainable, opt, frozen, tokens) -> (trainable, opt,
    loss, clipped gradient)``; ``tokens`` holds ``seq + 1`` ids a row.
    ``rows`` keeps only the first rows of the batch (a planted fault)."""
    @jax.jit
    def step(tr, opt, frozen, toks):
        if rows is not None:
            toks = toks[:rows]
        loss, g = jax.value_and_grad(loss_fn)(
            tr, frozen, toks[:, :-1], toks[:, 1:], m, mode)
        tr, opt, gc = ref_clip.adam_step(g, opt, tr, lr=lr)
        return tr, opt, loss, gc
    return step
