"""Unified model facade for every assigned architecture family.

A ``Model`` exposes:
  init_params(rng)      -> {"frozen": ..., "trainable": {"lora", "adapter"}}
  param_specs()         -> same pytree of ShapeDtypeStructs (dry-run)
  forward(...)          -> logits, aux             (train shapes)
  train_step(...)       -> TriplePlay local client step (LoRA+adapter only)
  prefill(...)          -> last-token logits, KV/state cache
  decode_step(...)      -> one-token logits, updated cache
  init_cache/cache_specs, input_specs

The frozen backbone may be quantized (cfg.quant_bits ∈ {0, 8, 4} with
linear or NF4 blocks); only LoRA pairs and the paper's attention adapter
are trainable — exactly TriplePlay's client-side configuration.

Layers are stacked and ``lax.scan``ned (hybrid RG-LRU/attention patterns
use a per-layer flag + ``lax.cond``) so HLO size and compile time are O(1)
in depth. ``cfg.first_k_dense`` layers (kimi-k2) are unrolled before the
scanned MoE stack. The ``hybrid_moe`` family (Jamba) stacks over periods:
each position of a period holds its own parameter tree (Mamba or
attention, experts or a dense SwiGLU), scanned over the periods.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ATTN, RGLRU, SSM, InputShape, ModelConfig
from repro.core import adapter as adapter_lib
from repro.core import losses, optim
from repro.core import lora as lora_lib
from repro.core.quant import QTensor, quantize_tree, quantize_tree_specs
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models import runtime as rt_lib

KIND_ID = {ATTN: 0, SSM: 1, RGLRU: 2}


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _dp(cfg):
    rt = rt_lib.get_runtime()
    return rt.dp_axes if rt else ("data",)


def _seq_axis(cfg, S):
    rt = rt_lib.get_runtime()
    if rt is None or not cfg.seq_shard or S <= 1 or S % rt.tp_size:
        return None
    return rt.tp_axis


# ================================================================ params
def positions(cfg: ModelConfig):
    """(mixer, feed-forward) of each position of one period of a
    ``hybrid_moe`` stack: the mixer from ``attn_pattern``, the
    feed-forward ``"moe"`` on expert layers and ``"mlp"`` elsewhere."""
    pat = cfg.attn_pattern
    return tuple((pat[j % len(pat)], "moe" if cfg.expert_layer(j) else "mlp")
                 for j in range(cfg.period))


def _lora_targets(cfg: ModelConfig, position=None) -> Dict[str, tuple]:
    d, qd, kvd, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    t: Dict[str, tuple] = {}
    fam = cfg.family
    if position is not None:      # hybrid_moe: experts and router frozen
        mixer, ffn = position
        if mixer == ATTN:
            t.update(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d))
        else:
            t.update(in_proj_x=(d, cfg.d_inner), out_proj=(cfg.d_inner, d))
        if ffn == "mlp":
            t.update(wg=(d, ff), wu=(d, ff), wd=(ff, d))
        return t
    if fam != "ssm":
        t.update(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d))
    if fam in ("dense", "vlm", "encdec"):
        t.update(wu=(d, ff), wd=(ff, d))
        if cfg.mlp == "swiglu":
            t["wg"] = (d, ff)
    if fam == "encdec":
        t.update(cwq=(d, qd), cwk=(d, kvd), cwv=(d, kvd), cwo=(qd, d))
    if fam == "ssm":
        t.update(in_proj_x=(d, cfg.d_inner), out_proj=(cfg.d_inner, d))
    if fam == "hybrid":
        w = cfg.lru_width or d
        t.update(wx=(d, w), wy=(d, w), out_proj=(w, d))
    return t


def _init_lora_layer(cfg, rng, position=None):
    t = _lora_targets(cfg, position)
    ks = jax.random.split(rng, len(t))
    tdt = jnp.dtype(cfg.trainable_dtype)
    return {n: lora_lib.init_pair(k, kk, nn, cfg.lora_rank, dtype=tdt)
            for (n, (kk, nn)), k in zip(sorted(t.items()), ks)}


def _lora_layer_specs(cfg, lead=()):
    t = _lora_targets(cfg)
    tdt = jnp.dtype(cfg.trainable_dtype)
    return {n: lora_lib.pair_specs(kk, nn, cfg.lora_rank, dtype=tdt,
                                   lead=lead)
            for n, (kk, nn) in sorted(t.items())}


def _init_layer(cfg: ModelConfig, rng, dtype, *, dense_ff: int = 0,
                encoder: bool = False):
    """One backbone layer of the arch family (dense variant if dense_ff)."""
    fam = cfg.family
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), jnp.float32)}
    if fam == "ssm":
        p.update(ssm_lib.init_mamba(ks[0], cfg, dtype))
        return p
    p.update(L.init_attention(ks[0], cfg, dtype))
    p["ln2"] = jnp.zeros((d,), jnp.float32)
    if encoder:
        p.update(L.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp, dtype))
        return p
    if fam == "encdec":
        p["lnc"] = jnp.zeros((d,), jnp.float32)
        p.update(L.init_attention(ks[2], cfg, dtype, cross=True))
        p.update(L.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp, dtype))
        return p
    if fam == "hybrid":
        p.update(rglru_lib.init_rglru(ks[3], cfg, dtype))
        p.update(L.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp, dtype))
        return p
    if fam == "moe" and not dense_ff:
        p["moe"] = moe_lib.init_experts(ks[4], cfg, dtype)
        if cfg.n_shared_experts:
            p["shared"] = L.init_mlp(
                ks[5], d, cfg.d_ff * cfg.n_shared_experts, "swiglu", dtype)
        return p
    ff = dense_ff or cfg.d_ff
    kind = "swiglu" if fam == "moe" else cfg.mlp
    p.update(L.init_mlp(ks[1], d, ff, kind, dtype))
    return p


def _layer_specs(cfg: ModelConfig, dtype, lead=(), *, dense_ff: int = 0,
                 encoder: bool = False):
    fam = cfg.family
    d = cfg.d_model
    f1 = jax.ShapeDtypeStruct((*lead, d), jnp.float32)
    p: Dict[str, Any] = {"ln1": f1}
    if fam == "ssm":
        p.update(ssm_lib.mamba_specs(cfg, dtype, lead))
        return p
    p.update(L.attention_specs(cfg, dtype, lead=lead))
    p["ln2"] = f1
    if encoder:
        p.update(L.mlp_specs(d, cfg.d_ff, cfg.mlp, dtype, lead))
        return p
    if fam == "encdec":
        p["lnc"] = f1
        p.update(L.attention_specs(cfg, dtype, cross=True, lead=lead))
        p.update(L.mlp_specs(d, cfg.d_ff, cfg.mlp, dtype, lead))
        return p
    if fam == "hybrid":
        p.update(rglru_lib.rglru_specs(cfg, dtype, lead))
        p.update(L.mlp_specs(d, cfg.d_ff, cfg.mlp, dtype, lead))
        return p
    if fam == "moe" and not dense_ff:
        p["moe"] = moe_lib.expert_specs(cfg, dtype, lead)
        if cfg.n_shared_experts:
            p["shared"] = L.mlp_specs(
                d, cfg.d_ff * cfg.n_shared_experts, "swiglu", dtype, lead)
        return p
    ff = dense_ff or cfg.d_ff
    kind = "swiglu" if fam == "moe" else cfg.mlp
    p.update(L.mlp_specs(d, ff, kind, dtype, lead))
    return p


def _restacked(tree):
    """QTensors made one layer (or expert) at a time and stacked by
    ``lax.map`` carry the unstacked shape; give them the stacked one."""
    def fix(l):
        if not isinstance(l, QTensor):
            return l
        *lead, G, _, N = l.q.shape
        return dataclasses.replace(l, orig_shape=(*lead, G * l.block, N))
    return jax.tree.map(fix, tree, is_leaf=lambda l: isinstance(l, QTensor))


def _init_position(cfg: ModelConfig, rng, dtype, position):
    """One layer of a ``hybrid_moe`` period, its frozen weights made and
    quantized here (the experts one at a time), so a stack of them never
    exists in bf16."""
    mixer, ffn = position
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    quant = (lambda t: quantize_tree(t, bits=cfg.quant_bits,
                                     block=cfg.quant_block,
                                     mode=cfg.quant_mode)) \
        if cfg.quant_bits else (lambda t: t)
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), jnp.float32),
                         "ln2": jnp.zeros((d,), jnp.float32)}
    if mixer == ATTN:
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    else:
        p["mamba"] = ssm_lib.init_mamba(ks[0], cfg, dtype)
    if ffn == "moe":
        p["moe"] = _restacked(moe_lib.init_experts(ks[4], cfg, dtype,
                                                   quantize=quant))
    else:
        p["mlp"] = L.init_mlp(ks[1], d, cfg.d_ff, "swiglu", dtype)
    return quant(p)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_scanned = cfg.n_layers - cfg.first_k_dense
        self.kinds = np.array(
            [KIND_ID[k] for k in cfg.layer_kinds()[cfg.first_k_dense:]],
            np.int32)
        self.hybrid = cfg.family == "hybrid"
        # hybrid_moe: parameters stacked over periods, each position of a
        # period with its own tree (mixer and feed-forward kinds differ)
        self.positions = positions(cfg) if cfg.family == "hybrid_moe" \
            else None

    # ---------------------------------------------------------- params
    def init_params(self, rng):
        cfg = self.cfg
        dt = _dtype(cfg)
        k_emb, k_head, k_lay, k_dense, k_enc, k_lora, k_ad, k_pos = \
            jax.random.split(rng, 8)
        frozen: Dict[str, Any] = {
            "embed": jax.random.normal(
                k_emb, (cfg.vocab_size, cfg.d_model), dt) * 0.02,
            "head": jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab_size), dt) /
            jnp.sqrt(jnp.asarray(cfg.d_model, dt)),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        if not cfg.use_rope and cfg.max_pos:
            frozen["pos_embed"] = jax.random.normal(
                k_pos, (cfg.max_pos, cfg.d_model), dt) * 0.02
        if self.positions is not None:
            return self._init_periods(frozen, k_lay, k_lora, k_ad)
        frozen["layers"] = jax.vmap(
            lambda k: _init_layer(cfg, k, dt))(
                jax.random.split(k_lay, self.n_scanned))
        if cfg.first_k_dense:
            frozen["dense_layers"] = [
                _init_layer(cfg, k, dt, dense_ff=cfg.dense_d_ff)
                for k in jax.random.split(k_dense, cfg.first_k_dense)]
        if cfg.encoder_layers:
            frozen["enc_layers"] = jax.vmap(
                lambda k: _init_layer(cfg, k, dt, encoder=True))(
                    jax.random.split(k_enc, cfg.encoder_layers))
            frozen["enc_pos"] = jax.random.normal(
                jax.random.fold_in(k_enc, 1),
                (cfg.n_frames, cfg.d_model), dt) * 0.02
            frozen["enc_final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.quant_bits:
            for key in ("layers", "dense_layers", "enc_layers"):
                if key in frozen:
                    frozen[key] = quantize_tree(
                        frozen[key], bits=cfg.quant_bits,
                        block=cfg.quant_block, mode=cfg.quant_mode)
        tdt = jnp.dtype(cfg.trainable_dtype)
        trainable: Dict[str, Any] = {
            "lora": jax.vmap(lambda k: _init_lora_layer(cfg, k))(
                jax.random.split(k_lora, self.n_scanned)),
            "adapter": adapter_lib.init(
                k_ad, cfg.d_model, n_heads=cfg.adapter_heads,
                d_ff=cfg.adapter_d_ff, dtype=tdt),
        }
        if cfg.first_k_dense:
            trainable["dense_lora"] = [
                _init_lora_layer(cfg, k)
                for k in jax.random.split(jax.random.fold_in(k_lora, 1),
                                          cfg.first_k_dense)]
        if cfg.encoder_layers:
            trainable["enc_lora"] = jax.vmap(
                lambda k: _enc_lora_init(cfg, k))(
                    jax.random.split(jax.random.fold_in(k_lora, 2),
                                     cfg.encoder_layers))
        return {"frozen": frozen, "trainable": trainable}

    def _init_periods(self, frozen, k_lay, k_lora, k_ad):
        """Layer ``i = period·p + j`` takes key ``i`` of ``k_lay``'s (and
        ``k_lora``'s) split; position ``j``'s trees stack over ``p``,
        made one layer at a time (``lax.map``)."""
        cfg = self.cfg
        dt = _dtype(cfg)
        P = len(self.positions)
        k_layers = jax.random.split(k_lay, cfg.n_layers)
        k_loras = jax.random.split(k_lora, cfg.n_layers)
        frozen["layers"] = [_restacked(jax.lax.map(
            lambda k, pos=pos: _init_position(cfg, k, dt, pos),
            k_layers[j::P])) for j, pos in enumerate(self.positions)]
        tdt = jnp.dtype(cfg.trainable_dtype)
        trainable = {
            "lora": [jax.vmap(lambda k, pos=pos: _init_lora_layer(
                cfg, k, pos))(k_loras[j::P])
                for j, pos in enumerate(self.positions)],
            "adapter": adapter_lib.init(
                k_ad, cfg.d_model, n_heads=cfg.adapter_heads,
                d_ff=cfg.adapter_d_ff, dtype=tdt)}
        return {"frozen": frozen, "trainable": trainable}

    def param_specs(self):
        cfg = self.cfg
        dt = _dtype(cfg)
        if self.positions is not None:
            return jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        S = lambda *sh, d=dt: jax.ShapeDtypeStruct(sh, d)
        frozen: Dict[str, Any] = {
            "embed": S(cfg.vocab_size, cfg.d_model),
            "head": S(cfg.d_model, cfg.vocab_size),
            "final_norm": S(cfg.d_model, d=jnp.float32),
        }
        if not cfg.use_rope and cfg.max_pos:
            frozen["pos_embed"] = S(cfg.max_pos, cfg.d_model)
        frozen["layers"] = _layer_specs(cfg, dt, lead=(self.n_scanned,))
        if cfg.first_k_dense:
            frozen["dense_layers"] = [
                _layer_specs(cfg, dt, dense_ff=cfg.dense_d_ff)
                for _ in range(cfg.first_k_dense)]
        if cfg.encoder_layers:
            frozen["enc_layers"] = _layer_specs(
                cfg, dt, lead=(cfg.encoder_layers,), encoder=True)
            frozen["enc_pos"] = S(cfg.n_frames, cfg.d_model)
            frozen["enc_final_norm"] = S(cfg.d_model, d=jnp.float32)
        if cfg.quant_bits:
            for key in ("layers", "dense_layers", "enc_layers"):
                if key in frozen:
                    frozen[key] = quantize_tree_specs(
                        frozen[key], bits=cfg.quant_bits,
                        block=cfg.quant_block, mode=cfg.quant_mode)
        tdt = jnp.dtype(cfg.trainable_dtype)
        trainable: Dict[str, Any] = {
            "lora": _lora_layer_specs(cfg, lead=(self.n_scanned,)),
            "adapter": adapter_lib.specs(
                cfg.d_model, d_ff=cfg.adapter_d_ff, dtype=tdt),
        }
        if cfg.first_k_dense:
            trainable["dense_lora"] = [
                _lora_layer_specs(cfg) for _ in range(cfg.first_k_dense)]
        if cfg.encoder_layers:
            trainable["enc_lora"] = _enc_lora_specs(
                cfg, lead=(cfg.encoder_layers,))
        return {"frozen": frozen, "trainable": trainable}

    # ---------------------------------------------------------- encoder
    def _encode(self, frozen, trainable, frames):
        cfg = self.cfg
        x = frames.astype(_dtype(cfg)) + frozen["enc_pos"][None]
        positions = jnp.arange(x.shape[1])

        def body(x, inp):
            p, lo = inp
            h, _ = L.attention(p, L.rms_norm(x, p["ln1"]), positions, cfg,
                               lora=lo, causal=False, use_rope=False)
            x = x + h
            x = x + L.mlp(p, L.rms_norm(x, p["ln2"]), cfg, lora=lo)
            return x, None

        xs = (frozen["enc_layers"], trainable["enc_lora"])
        if cfg.unroll_layers:
            for i in range(cfg.encoder_layers):
                x, _ = body(x, jax.tree.map(lambda l: l[i], xs))
            return L.rms_norm(x, frozen["enc_final_norm"])
        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = lax.scan(body, x, xs)
        return L.rms_norm(x, frozen["enc_final_norm"])

    # ---------------------------------------------------------- blocks
    def _block(self, p, lo, x, positions, enc_out, mode, cache=None,
               pos=None, cache_len=None, kind=0):
        """One layer. mode: 'train' | 'prefill' | 'decode'.
        Returns (x, cache_entry, aux)."""
        cfg = self.cfg
        fam = cfg.family
        decode = mode == "decode"
        aux = jnp.zeros((), jnp.float32)

        @jax.named_scope("attn")
        def attn_part(x):
            xin = L.rms_norm(x, p["ln1"])
            if decode:
                h, kv = L.attention_decode(
                    p, xin, pos, cache["kv"], cfg, lora=lo,
                    use_rope=cfg.use_rope)
            else:
                h, (k, v) = L.attention(
                    p, xin, positions, cfg, lora=lo, causal=True,
                    window=cfg.window, use_rope=cfg.use_rope)
                kv = L.ring_from_full(
                    k, v, cache_len, kv_quant=cfg.kv_quant_bits == 8) \
                    if mode == "prefill" else None
            return x + h, kv

        def lru_part(x):
            xin = L.rms_norm(x, p["ln1"])
            if decode:
                h, st = rglru_lib.rglru_decode(p, xin, cache["lru"], cfg,
                                               lora=lo)
            else:
                h, st = rglru_lib.rglru_block(p, xin, cfg, lora=lo)
                st = st if mode == "prefill" else None
            return x + h, st

        if fam == "ssm":
            xin = L.rms_norm(x, p["ln1"])
            if decode:
                h, st = ssm_lib.mamba_decode(p, xin, cache["ssm"], cfg,
                                             lora=lo)
            else:
                h, st = ssm_lib.mamba_block(p, xin, cfg, lora=lo)
            return x + h, {"ssm": st}, aux

        if fam == "hybrid":
            B = x.shape[0]
            M = cache["kv"]["k"].shape[1] if decode else cache_len
            # hybrid layers skip the outer scan-body remat (see _stack);
            # attention/MLP get their own checkpoints here, the RG-LRU
            # block checkpoints inside its shard_map body
            inner_remat = jax.checkpoint if (cfg.remat and mode == "train") \
                else (lambda f: f)

            def attn_branch(x):
                xa, kv = inner_remat(attn_part)(x)
                dummy = _dummy_lru(cfg, B, _dtype(cfg)) \
                    if mode != "train" else None
                return xa, {"kv": kv, "lru": dummy} if mode != "train" \
                    else {"kv": None, "lru": None}

            def lru_branch(x):
                xl, st = lru_part(x)
                dummy = _dummy_kv(cfg, B, M, _dtype(cfg)) \
                    if mode != "train" else None
                return xl, {"kv": dummy, "lru": st} if mode != "train" \
                    else {"kv": None, "lru": None}

            x, entry = lax.cond(kind == KIND_ID[ATTN], attn_branch,
                                lru_branch, x)
            mlp_fn = inner_remat(
                lambda h: L.mlp(p, L.rms_norm(h, p["ln2"]), cfg, lora=lo))
            x = x + mlp_fn(x)
            return x, entry, aux

        # attention families: dense / moe / vlm / encdec
        x, kv = attn_part(x)
        entry = {"kv": kv}
        if fam == "encdec":
            xin = L.rms_norm(x, p["lnc"])
            if decode:
                h, _ = L.attention_decode(
                    p, xin, pos, cache["ckv"], cfg, lora=lo, prefix="c",
                    use_rope=False, update_cache=False)
                entry["ckv"] = cache["ckv"]
            else:
                h, (ck, cv) = L.attention(
                    p, xin, positions, cfg, lora=lo, prefix="c",
                    causal=False, kv_x=enc_out, use_rope=False)
                entry["ckv"] = {"k": ck, "v": cv,
                                "slot_pos": jnp.arange(ck.shape[1],
                                                       dtype=jnp.int32)} \
                    if mode == "prefill" else None
            x = x + h
        with jax.named_scope("mlp"):
            if fam == "moe" and "moe" in p:
                y, aux = moe_lib.moe_ffn(p["moe"], L.rms_norm(x, p["ln2"]),
                                         cfg)
                if cfg.n_shared_experts:
                    y = y + L.mlp(p["shared"], L.rms_norm(x, p["ln2"]), cfg,
                                  kind="swiglu")
                x = x + y
            else:
                kind_mlp = "swiglu" if fam == "moe" else cfg.mlp
                x = x + L.mlp(p, L.rms_norm(x, p["ln2"]), cfg, lora=lo,
                              kind=kind_mlp)
        return x, entry, aux

    # ---------------------------------------------------------- forward
    def _embed_inputs(self, frozen, batch, mode):
        cfg = self.cfg
        dt = _dtype(cfg)
        tokens = batch["tokens"]
        x = jnp.take(frozen["embed"], tokens, axis=0).astype(dt)
        if cfg.family == "vlm" and "image_embeds" in batch:
            img = batch["image_embeds"].astype(dt)
            x = jnp.concatenate([img, x], axis=1)
        S = x.shape[1]
        if mode == "decode":
            positions = None
        else:
            positions = jnp.arange(S)
            if not cfg.use_rope and cfg.max_pos:
                x = x + jnp.take(frozen["pos_embed"],
                                 jnp.minimum(positions, cfg.max_pos - 1),
                                 axis=0)[None]
        return x, positions

    def _stack(self, frozen, trainable, x, positions, enc_out, mode,
               cache=None, pos=None, cache_len=None):
        cfg = self.cfg
        dp = _dp(cfg)
        seq_ax = _seq_axis(cfg, x.shape[1])
        kinds = jnp.asarray(self.kinds)

        # unrolled first-k-dense layers (kimi-k2)
        new_dense_cache = []
        for i in range(cfg.first_k_dense):
            c = None if cache is None else \
                jax.tree.map(lambda l: l[i], cache["dense"])
            x, entry, _ = self._block(
                frozen["dense_layers"][i], trainable["dense_lora"][i], x,
                positions, enc_out, mode, cache=c, pos=pos,
                cache_len=cache_len)
            new_dense_cache.append(entry)
            x = rt_lib.constrain(x, dp, seq_ax, None)

        def body(carry, inp):
            x, aux = carry
            p, lo, kind, c = inp
            x, entry, a = self._block(p, lo, x, positions, enc_out, mode,
                                      cache=c, pos=pos,
                                      cache_len=cache_len, kind=kind)
            x = rt_lib.constrain(x, dp, seq_ax, None)
            return (x, aux + a), entry

        # scan-body remat — except for recurrent families, where wrapping
        # the shard_map'd chunked scan in jax.checkpoint compiles
        # pathologically slowly (25+ min vs 17 s); those blocks checkpoint
        # inside their shard_map bodies instead (models/ssm.py).
        if cfg.remat and mode == "train" and \
                cfg.family not in ("ssm", "hybrid"):
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

        scan_cache = None if cache is None else cache["scan"]
        xs = (frozen["layers"], trainable["lora"], kinds, scan_cache)
        if cfg.unroll_layers:  # dry-run cost calibration: no while loop
            carry = (x, jnp.zeros((), jnp.float32))
            entries_list = []
            for i in range(self.n_scanned):
                xi = jax.tree.map(lambda l: l[i], xs)
                carry, e = body(carry, xi)
                entries_list.append(e)
            x, aux = carry
            entries = None
            if entries_list and entries_list[0] is not None and \
                    jax.tree.leaves(entries_list[0]):
                entries = jax.tree.map(lambda *ls: jnp.stack(ls),
                                       *entries_list)
        else:
            (x, aux), entries = lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), xs)
        new_cache = None
        if mode in ("prefill", "decode"):
            new_cache = {"scan": entries}
            if cfg.first_k_dense:
                new_cache["dense"] = jax.tree.map(
                    lambda *ls: jnp.stack(ls), *new_dense_cache) \
                    if len(new_dense_cache) > 1 else jax.tree.map(
                        lambda l: l[None], new_dense_cache[0])
        return x, aux, new_cache

    # ---------------------------------------------------------- periods
    def _position_block(self, j, p, lo, x, positions):
        """Position ``j`` of a ``hybrid_moe`` period (training shapes).
        Returns (x, aux, the largest expert load, pad rows)."""
        cfg = self.cfg
        mixer, ffn = self.positions[j]
        xin = L.rms_norm(x, p["ln1"])
        if mixer == ATTN:
            with jax.named_scope("attn"):
                h, _ = L.attention(p["attn"], xin, positions, cfg, lora=lo,
                                   causal=True, window=cfg.window,
                                   use_rope=cfg.use_rope)
        else:
            with jax.named_scope("mamba"):
                h, _ = ssm_lib.mamba_block(p["mamba"], xin, cfg, lora=lo)
        x = x + h
        xin = L.rms_norm(x, p["ln2"])
        if ffn == "moe":
            with jax.named_scope("moe"):
                y, aux, st = moe_lib.moe_dropless(
                    p["moe"], xin.reshape(-1, cfg.d_model), cfg)
            return (x + y.reshape(x.shape), aux, st["moe_load_max"],
                    st["moe_pad_rows"])
        with jax.named_scope("mlp"):
            y = L.mlp(p["mlp"], xin, cfg, lora=lo, kind="swiglu")
        zero = jnp.zeros((), jnp.float32)
        return x + y, zero, zero, zero

    def _period_stack(self, frozen, trainable, x, positions):
        """``lax.scan`` over periods; inside, each position is its own
        layer with its own remat (``nothing_saveable``), so the backward
        keeps one layer's residuals live at a time. Returns (x, aux,
        {moe_load_max, moe_pad_rows})."""
        def layer(j):
            f = lambda p, lo, x: self._position_block(j, p, lo, x, positions)
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.nothing_saveable) \
                if self.cfg.remat else f

        fns = [layer(j) for j in range(len(self.positions))]

        def body(carry, inp):
            x, aux, load, pad = carry
            ps, los = inp
            for f, p, lo in zip(fns, ps, los):
                x, a, ld, pd = f(p, lo, x)
                aux, load, pad = aux + a, jnp.maximum(load, ld), pad + pd
            return (x, aux, load, pad), None

        z = jnp.zeros((), jnp.float32)
        (x, aux, load, pad), _ = lax.scan(
            body, (x, z, z, z), (frozen["layers"], trainable["lora"]))
        return x, aux, {"moe_load_max": load, "moe_pad_rows": pad}

    def forward(self, frozen, trainable, batch):
        """Training-shape forward. Returns (logits, moe aux loss)."""
        logits, aux, _ = self._forward(frozen, trainable, batch)
        return logits, aux

    def _forward(self, frozen, trainable, batch):
        cfg = self.cfg
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._encode(frozen, trainable, batch["frames"])
        x, positions = self._embed_inputs(frozen, batch, "train")
        stats = {}
        if self.positions is not None:
            x, aux, stats = self._period_stack(frozen, trainable, x,
                                               positions)
        else:
            x, aux, _ = self._stack(frozen, trainable, x, positions,
                                    enc_out, "train")
        x = L.rms_norm(x, frozen["final_norm"])
        x = adapter_lib.apply(trainable["adapter"], x,
                              n_heads=cfg.adapter_heads, causal=True)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            frozen["head"].astype(x.dtype))
        # keep logits vocab-replicated / seq-sharded so the CE gather and
        # logsumexp stay local (a vocab-sharded CE gather all-gathers the
        # full (B,S,V) logits — measured 16 GiB/device on yi-9b train_4k)
        logits = rt_lib.constrain(logits, _dp(cfg),
                                  _seq_axis(cfg, logits.shape[1]), None)
        return logits, aux, stats

    # ---------------------------------------------------------- training
    @jax.named_scope("forward")
    def loss_fn(self, frozen, trainable, batch):
        """Its ops sit in the ``forward`` scope: under ``value_and_grad``
        the forward's op names carry ``jvp(forward)``, the backward's
        ``transpose(jvp(forward))``, a remat recompute's
        ``rematted_computation``. A dropless MoE adds its routing
        statistics to the parts (``moe_load_max``, ``moe_pad_rows``)."""
        logits, aux, stats = self._forward(frozen, trainable, batch)
        mask = batch.get("mask")
        ce = losses.cross_entropy(logits, batch["labels"], mask)
        return ce + 0.01 * aux, {"ce": ce, "aux": aux, **stats}

    def train_step(self, frozen, trainable, opt_state, batch, *,
                   lr=1e-4):
        """One TriplePlay local client step: grads w.r.t. LoRA+adapter only.
        cfg.grad_accum > 1 scans microbatches and accumulates grads (the
        §Perf memory lever for the big-batch training shapes)."""
        A = self.cfg.grad_accum
        if A > 1:
            def micro(carry, mb):
                (loss, parts), g = jax.value_and_grad(
                    lambda tr: self.loss_fn(frozen, tr, mb),
                    has_aux=True)(trainable)
                acc, losses = carry
                acc = jax.tree.map(lambda a, b: a + b / A, acc, g)
                return (acc, losses + loss / A), None
            mbs = jax.tree.map(
                lambda l: l.reshape(A, l.shape[0] // A, *l.shape[1:]),
                batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), trainable)
            (grads, loss), _ = lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), mbs)
            parts = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        else:
            (loss, parts), grads = jax.value_and_grad(
                lambda tr: self.loss_fn(frozen, tr, batch), has_aux=True)(
                    trainable)
        trainable, opt_state = optim.adam_update(
            grads, opt_state, trainable, lr=lr, grad_clip=1.0)
        metrics = {"loss": loss, **parts,
                   "grad_norm": optim.global_norm(grads)}
        return trainable, opt_state, metrics

    # ---------------------------------------------------------- serving
    def _trains_only(self):
        if self.positions is not None:
            raise NotImplementedError(
                f"{self.cfg.family}: the period stack has no prefill or "
                "decode path yet; it trains (Model.train_step)")

    def effective_cache_len(self, context_len: int) -> int:
        if self.cfg.window:
            return min(context_len, self.cfg.window)
        return context_len

    def prefill(self, frozen, trainable, batch, max_len: int | None = None):
        """Returns (last-token logits (B, V), cache).

        ``max_len`` sizes the emitted cache (defaults to the prompt length);
        pass the serving context length so subsequent ``decode_step`` calls
        have room (sliding-window archs cap at the window regardless)."""
        cfg = self.cfg
        self._trains_only()
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._encode(frozen, trainable, batch["frames"])
        x, positions = self._embed_inputs(frozen, batch, "prefill")
        M = self.effective_cache_len(max_len or x.shape[1])
        x, aux, cache = self._stack(frozen, trainable, x, positions,
                                    enc_out, "prefill", cache_len=M)
        x = L.rms_norm(x, frozen["final_norm"])
        x, acache = adapter_lib.prefill(
            trainable["adapter"], x,
            min(max_len or x.shape[1], cfg.adapter_window),
            n_heads=cfg.adapter_heads)
        cache["adapter"] = acache
        logits = jnp.einsum("bsd,dv->bsv", x,
                            frozen["head"].astype(x.dtype))[:, 0]
        return logits, cache

    def decode_step(self, frozen, trainable, cache, tokens, pos):
        """tokens: (B, 1); pos: scalar int32. Returns (logits (B, V), cache)."""
        cfg = self.cfg
        self._trains_only()
        dt = _dtype(cfg)
        x = jnp.take(frozen["embed"], tokens, axis=0).astype(dt)
        if not cfg.use_rope and cfg.max_pos:
            x = x + jnp.take(frozen["pos_embed"],
                             jnp.minimum(pos, cfg.max_pos - 1),
                             axis=0)[None, None]
        acache = cache["adapter"]
        x, _, cache = self._stack(frozen, trainable, x, None, None,
                                  "decode", cache=cache, pos=pos)
        x = L.rms_norm(x, frozen["final_norm"])
        x, acache = adapter_lib.decode(trainable["adapter"], x, acache,
                                       pos, n_heads=cfg.adapter_heads)
        cache["adapter"] = acache
        logits = jnp.einsum("bsd,dv->bsv", x,
                            frozen["head"].astype(x.dtype))[:, 0]
        return logits, cache

    # ---------------------------------------------------------- caches
    def _entry_specs(self, batch, M, dt, init=False):
        """Per-layer cache entry (spec or zeros)."""
        cfg = self.cfg
        fam = cfg.family
        mk = (lambda tree: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype) if s.dtype != jnp.int32
            else jnp.full(s.shape, -1, jnp.int32), tree)) if init else \
            (lambda tree: tree)
        if fam == "ssm":
            return {"ssm": mk(ssm_lib.mamba_cache_specs(cfg, batch, dt))}
        kv = mk(L.kv_cache_specs(cfg, batch, M, dt))
        if fam == "hybrid":
            return {"kv": kv,
                    "lru": mk(rglru_lib.rglru_cache_specs(cfg, batch, dt))}
        entry = {"kv": kv}
        if fam == "encdec":
            ck = L.kv_cache_specs(cfg, batch, cfg.n_frames, dt)
            entry["ckv"] = mk(ck)
        return entry

    def cache_specs(self, batch: int, context_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        M = self.effective_cache_len(context_len)
        one = self._entry_specs(batch, M, dt)
        stack = lambda tree, n: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), tree)
        out = {"scan": stack(one, self.n_scanned)}
        if cfg.first_k_dense:
            out["dense"] = stack(one, cfg.first_k_dense)
        out["adapter"] = adapter_lib.cache_specs(
            cfg.d_model, batch, min(context_len, cfg.adapter_window), dt,
            n_heads=cfg.adapter_heads)
        return out

    def init_cache(self, batch: int, context_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        M = self.effective_cache_len(context_len)
        one = self._entry_specs(batch, M, dt, init=True)
        stack = lambda tree, n: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n, *a.shape)).copy(), tree)
        out = {"scan": stack(one, self.n_scanned)}
        if cfg.first_k_dense:
            out["dense"] = stack(one, cfg.first_k_dense)
        aspec = adapter_lib.cache_specs(
            cfg.d_model, batch, min(context_len, cfg.adapter_window), dt,
            n_heads=cfg.adapter_heads)
        out["adapter"] = jax.tree.map(
            lambda s: jnp.full(s.shape, -1, jnp.int32)
            if s.dtype == jnp.int32 else jnp.zeros(s.shape, s.dtype), aspec)
        return out

    # ---------------------------------------------------------- inputs
    def input_specs(self, shape: InputShape) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of a step."""
        cfg = self.cfg
        dt = _dtype(cfg)
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            S_text = S - cfg.n_patches if cfg.family == "vlm" else S
            specs = {"tokens": jax.ShapeDtypeStruct((B, S_text), i32),
                     "labels": jax.ShapeDtypeStruct((B, S), i32),
                     "mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
            if cfg.family == "vlm":
                specs["image_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.d_model), dt)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_frames, cfg.d_model), dt)
            return specs
        if shape.kind == "prefill":
            S_text = S - cfg.n_patches if cfg.family == "vlm" else S
            specs = {"tokens": jax.ShapeDtypeStruct((B, S_text), i32)}
            if cfg.family == "vlm":
                specs["image_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_patches, cfg.d_model), dt)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.n_frames, cfg.d_model), dt)
            return specs
        # decode
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "pos": jax.ShapeDtypeStruct((), i32),
                "cache": self.cache_specs(B, S)}


# ---------------------------------------------------------------- helpers
def _enc_lora_init(cfg, rng):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    t = dict(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d))
    ks = jax.random.split(rng, len(t))
    tdt = jnp.dtype(cfg.trainable_dtype)
    return {n: lora_lib.init_pair(k, kk, nn, cfg.lora_rank, dtype=tdt)
            for (n, (kk, nn)), k in zip(sorted(t.items()), ks)}


def _enc_lora_specs(cfg, lead=()):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    t = dict(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d))
    tdt = jnp.dtype(cfg.trainable_dtype)
    return {n: lora_lib.pair_specs(kk, nn, cfg.lora_rank, dtype=tdt,
                                   lead=lead)
            for n, (kk, nn) in sorted(t.items())}


def _dummy_kv(cfg, B, M, dt):
    sh = (B, M, cfg.n_kv_heads, cfg.head_dim)
    kdt = jnp.int8 if cfg.kv_quant_bits == 8 else dt
    c = {"k": jnp.zeros(sh, kdt), "v": jnp.zeros(sh, kdt),
         "slot_pos": jnp.full((M,), -1, jnp.int32)}
    if cfg.kv_quant_bits == 8:
        c["k_scale"] = jnp.zeros((*sh[:3], 1), jnp.float32)
        c["v_scale"] = jnp.zeros((*sh[:3], 1), jnp.float32)
    return c


def _dummy_lru(cfg, B, dt):
    w, K = cfg.lru_width or cfg.d_model, cfg.ssm_conv
    return {"h": jnp.zeros((B, w), jnp.float32),
            "conv": jnp.zeros((B, K - 1, w), dt)}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
