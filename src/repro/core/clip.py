"""CLIP-style dual encoder — the paper's foundation model (ref [1]).

A compact, self-contained ViT image encoder + text transformer trained with
the symmetric contrastive loss, sized for CPU-scale FL simulation (the
full-size transformer stacks live in repro.models; this module is the
*functional* CLIP used by the federated experiments). Zero-shot
classification = cosine(image embedding, class-prompt text embeddings).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core import lora as lora_lib


@dataclass(frozen=True)
class CLIPConfig:
    image_size: int = 32
    patch: int = 8
    channels: int = 3
    vision_layers: int = 2
    text_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    d_ff: int = 128
    vocab: int = 512
    max_text_len: int = 8
    proj_dim: int = 32

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch) ** 2


def _init_block(rng, d, d_ff, dtype=jnp.float32):
    ks = jax.random.split(rng, 6)
    s = lambda f: 1.0 / jnp.sqrt(f)
    return {"ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
            "wq": jax.random.normal(ks[0], (d, d), dtype) * s(d),
            "wk": jax.random.normal(ks[1], (d, d), dtype) * s(d),
            "wv": jax.random.normal(ks[2], (d, d), dtype) * s(d),
            "wo": jax.random.normal(ks[3], (d, d), dtype) * s(d),
            "wu": jax.random.normal(ks[4], (d, d_ff), dtype) * s(d),
            "wd": jax.random.normal(ks[5], (d_ff, d), dtype) * s(d_ff)}


def init_clip(rng, cfg: CLIPConfig):
    ks = jax.random.split(rng, 10)
    d = cfg.d_model
    pdim = cfg.patch * cfg.patch * cfg.channels
    s = lambda f: 1.0 / jnp.sqrt(f)
    vision = {
        "patch_embed": jax.random.normal(ks[0], (pdim, d)) * s(pdim),
        "cls": jax.random.normal(ks[1], (d,)) * 0.02,
        "pos": jax.random.normal(ks[2], (cfg.n_patches + 1, d)) * 0.02,
        "blocks": jax.vmap(lambda k: _init_block(k, d, cfg.d_ff))(
            jax.random.split(ks[3], cfg.vision_layers)),
        "ln": jnp.zeros((d,)),
    }
    text = {
        "embed": jax.random.normal(ks[4], (cfg.vocab, d)) * 0.02,
        "pos": jax.random.normal(ks[5], (cfg.max_text_len, d)) * 0.02,
        "blocks": jax.vmap(lambda k: _init_block(k, d, cfg.d_ff))(
            jax.random.split(ks[6], cfg.text_layers)),
        "ln": jnp.zeros((d,)),
    }
    return {"vision": vision, "text": text,
            "proj_v": jax.random.normal(ks[7], (d, cfg.proj_dim)) * s(d),
            "proj_t": jax.random.normal(ks[8], (d, cfg.proj_dim)) * s(d),
            "logit_scale": jnp.asarray(jnp.log(1 / 0.07))}


def _ln(x, w, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1 + w)


# TriplePlay's fixed LoRA scaling alpha/r for the CLIP blocks: the lin
# closure historically hard-coded `delta * 2.0`; routing through
# lora_lib.linear keeps that exact factor (alpha = LORA_SCALE * r).
LORA_SCALE = 2.0


def _block(p, x, n_heads, causal=False, lora=None):
    B, S, d = x.shape
    dh = d // n_heads

    def lin(name, h):
        la = None if lora is None else lora.get(name)
        if la is not None:
            r = la["a"].shape[-1]
            # fused base+LoRA op (kernels.ops.lora_matmul): one kernel,
            # fp32 accumulation, custom VJP
            return lora_lib.linear(h, p[name], la,
                                   alpha=LORA_SCALE * r, rank=r)
        return lora_lib.linear(h, p[name])

    with jax.named_scope("attn"):
        h = _ln(x, p["ln1"])
        q = lin("wq", h).reshape(B, S, n_heads, dh)
        k = lin("wk", h).reshape(B, S, n_heads, dh)
        v = lin("wv", h).reshape(B, S, n_heads, dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        a = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
        x = x + lin("wo", o)
    with jax.named_scope("mlp"):
        h = _ln(x, p["ln2"])
        return x + lora_lib.linear(jax.nn.gelu(
            lora_lib.linear(h, p["wu"])), p["wd"])


def _run_blocks(blocks, x, n_heads, causal, lora=None):
    L = jax.tree.leaves(blocks)[0].shape[0]
    for i in range(L):
        bp = jax.tree.map(lambda l: l[i], blocks)
        bl = None if lora is None else jax.tree.map(lambda l: l[i], lora)
        x = _block(bp, x, n_heads, causal, bl)
    return x


def patchify(images, patch):
    """(B, H, W, C) -> (B, n_patches, patch*patch*C)."""
    B, H, W, C = images.shape
    gh, gw = H // patch, W // patch
    x = images.reshape(B, gh, patch, gw, patch, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, gh * gw, -1)


def embed_patches(params, cfg: CLIPConfig, images):
    """(B, H, W, C) -> (B, n_patches + 1, d) embedded tokens (patch
    projection + cls + positions). Trainable-independent: LoRA/adapters
    never touch it, so batched executors hoist it out of training loops
    (computed once per staged data pool)."""
    v = params["vision"]
    x = patchify(images, cfg.patch) @ v["patch_embed"]
    cls = jnp.broadcast_to(v["cls"], (x.shape[0], 1, cfg.d_model))
    return jnp.concatenate([cls, x], axis=1) + v["pos"][None]


def encode_tokens(params, cfg: CLIPConfig, x, *, lora=None,
                  pool: bool = True):
    """Vision tower over pre-embedded tokens from ``embed_patches``."""
    v = params["vision"]
    x = _run_blocks(v["blocks"], x, cfg.n_heads, False, lora)
    x = _ln(x, v["ln"])
    return x[:, 0] if pool else x            # cls token


def encode_image(params, cfg: CLIPConfig, images, *, lora=None,
                 pool: bool = True):
    return encode_tokens(params, cfg, embed_patches(params, cfg, images),
                         lora=lora, pool=pool)


def encode_text(params, cfg: CLIPConfig, tokens):
    t = params["text"]
    x = t["embed"][tokens] + t["pos"][None, :tokens.shape[1]]
    x = _run_blocks(t["blocks"], x, cfg.n_heads, True)
    x = _ln(x, t["ln"])
    return x[:, -1]                            # last token


def image_embedding(params, cfg: CLIPConfig, images, *, lora=None):
    return encode_image(params, cfg, images, lora=lora) @ params["proj_v"]


def text_embedding(params, cfg: CLIPConfig, tokens):
    return encode_text(params, cfg, tokens) @ params["proj_t"]


def contrastive_loss(params, cfg: CLIPConfig, images, tokens):
    ie = image_embedding(params, cfg, images)
    te = text_embedding(params, cfg, tokens)
    return losses.clip_contrastive(ie, te, params["logit_scale"])


def zero_shot_logits(img_emb, class_text_emb, logit_scale):
    ie = img_emb / (jnp.linalg.norm(img_emb, axis=-1, keepdims=True) + 1e-8)
    te = class_text_emb / (jnp.linalg.norm(
        class_text_emb, axis=-1, keepdims=True) + 1e-8)
    return jnp.exp(logit_scale) * ie @ te.T
