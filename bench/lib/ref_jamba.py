"""Plain reference of one local QLoRA training step of Jamba2-Mini's
layers (Mamba-1 and GQA attention mixers, dense SwiGLU and 16-expert
top-2 MoE feed-forwards, arXiv:2403.19887), with LoRA, the attention
adapter and the frozen output head.

Float32 at ``highest`` matmul precision, no kernels and no code of the
program. The frozen weights are drawn again from each layer's key by the
law of ``repro.models.Model.init_params`` (in the configuration's dtype)
and go through the published NF4 codebook (every matrix of at least
4,096 entries, as QLoRA's quantizer takes them); they are drawn inside
each layer's (and each expert's) rematerialized body, so no more than
one expert's weights exist at a time.

- Mamba: the recurrence is a plain ``lax.scan`` over time; the conv has
  its bias, Δ, B and C their RMSNorms (Jamba's ``dt/b/c_layernorm``).
- Attention: causal, grouped-query, no positional encoding.
- MoE: a softmax router over all experts, top-k, the gates not
  renormalized; each expert runs over every token with the weight its
  gate gives where the token routed to it and 0 elsewhere, expert by
  expert: no capacity, no token dropped. The Switch load-balance term
  joins the loss at 0.01, as ``Model.loss_fn`` adds it.

``mode="fp8"`` is the control: every matrix product rounds both operands
to float8 e4m3 under a per-tensor scale (``ref_clip.fp8``), the step
below the configuration's bfloat16. Each sublayer (a layer's mixer, then
its feed-forward) is rematerialized in the backward pass, which changes
no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib import ref_clip, ref_trunk

F32 = jnp.float32


def sizes(config: dict) -> dict:
    """The widths and layer pattern a configuration file states."""
    m = config["model"]
    d = m["d_model"]
    c = dict(d=d, L=m["n_layers"], H=m["n_heads"], Hkv=m["n_kv_heads"],
             D=m["head_dim"], ff=m["d_ff"], V=m["vocab_size"],
             E=m["n_experts"], k=m["experts_per_token"], N=m["ssm_state"],
             K=m["ssm_conv"], di=m["ssm_expand"] * d, R=m["ssm_dt_rank"],
             r=m["lora_rank"], scale=m["lora_alpha"] / m["lora_rank"],
             block=m["quant_block"], dtype=jnp.dtype(m["dtype"]),
             Ha=m["adapter_heads"], ad_ff=m["adapter_d_ff"] or d)
    ap, ao = config["attn_layer_period"], config["attn_layer_offset"]
    ep, eo = config["expert_layer_period"], config["expert_layer_offset"]
    c["period"] = math.lcm(ap, ep)
    c["kinds"] = [("attn" if j % ap == ao else "mamba",
                   "moe" if j % ep == eo else "mlp")
                  for j in range(c["period"])]
    return c


def _w(k, shape, dt, fan):
    """The program's law: a normal draw in ``dt`` times 1/sqrt(fan)."""
    return jax.random.normal(k, shape, dt) * (1.0 / jnp.sqrt(fan))


def nf4_roundtrip(w, block: int):
    """``w (K, N)`` to NF4 in blocks of ``block`` along K (absmax scale,
    nearest code, the first on a tie) and back, one code at a time:
    element-wise work, no table gather (a TPU runs those one scalar at a
    time)."""
    K, N = w.shape
    xb = w.reshape(K // block, block, N)
    absmax = jnp.maximum(jnp.max(jnp.abs(xb), axis=-2, keepdims=True),
                         1e-12)
    v = xb / absmax
    best, val = jnp.abs(v - ref_clip.NF4[0]), jnp.full_like(
        v, ref_clip.NF4[0])
    for c in ref_clip.NF4[1:]:
        d = jnp.abs(v - c)
        val = jnp.where(d < best, c, val)
        best = jnp.minimum(d, best)
    return (val * absmax).reshape(w.shape)


def _frozen(w, block):
    """A frozen matrix as the program stores it: NF4 in blocks along K
    when it has at least 4,096 entries, else as drawn."""
    w = w.astype(F32)
    if w.size < 4096:
        return w
    return nf4_roundtrip(w, ref_clip.pick_block(w.shape[-2], block))


def _lora_shapes(c, kind):
    d, qd, kvd, di, ff = c["d"], c["H"] * c["D"], c["Hkv"] * c["D"], \
        c["di"], c["ff"]
    mixer, ffn = kind
    t = dict(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d)) \
        if mixer == "attn" else dict(in_proj_x=(d, di), out_proj=(di, d))
    if ffn == "mlp":
        t.update(wg=(d, ff), wu=(d, ff), wd=(ff, d))
    return t


def init(k, config: dict):
    """(frozen: embedding, head and one key per layer; trainable: LoRA
    per position of the period, stacked over periods, and the
    adapter)."""
    c = sizes(config)
    dt, d, V, L, P = c["dtype"], c["d"], c["V"], c["L"], c["period"]
    k_emb, k_head, k_lay, _, _, k_lora, k_ad, _ = jax.random.split(k, 8)
    frozen = {
        "embed": (jax.random.normal(k_emb, (V, d), dt) * 0.02).astype(F32),
        "head": (jax.random.normal(k_head, (d, V), dt) /
                 jnp.sqrt(jnp.asarray(d, dt))).astype(F32),
        "keys": jax.random.split(k_lay, L)}

    def lora(kk, kind):
        t = _lora_shapes(c, kind)
        ks = jax.random.split(kk, len(t))
        return {n: {"a": jax.random.normal(kx, (a, c["r"]), F32) *
                    (1.0 / jnp.sqrt(a)),
                    "b": jnp.zeros((c["r"], b), F32)}
                for (n, (a, b)), kx in zip(sorted(t.items()), ks)}

    k_loras = jax.random.split(k_lora, L)
    ka = jax.random.split(k_ad, 6)
    sd = 1.0 / jnp.sqrt(d)
    adf = c["ad_ff"]
    trainable = {
        "lora": [jax.vmap(lambda kk, kind=kind: lora(kk, kind))(
            k_loras[j::P]) for j, kind in enumerate(c["kinds"])],
        "adapter": {
            "wq": jax.random.normal(ka[0], (d, d), F32) * sd,
            "wk": jax.random.normal(ka[1], (d, d), F32) * sd,
            "wv": jax.random.normal(ka[2], (d, d), F32) * sd,
            "wo": jnp.zeros((d, d), F32),
            "w1": jax.random.normal(ka[3], (d, adf), F32) * sd,
            "b1": jnp.zeros((adf,), F32),
            "w2": jnp.zeros((adf, d), F32),
            "b2": jnp.zeros((d,), F32)}}
    return frozen, trainable


def _rms(x):
    return ref_trunk.rms_norm(x, 0.0)


def _attention(h, k0, lo, c, mm, lin):
    B, S, _ = h.shape
    dt, d, H, Hkv, D = c["dtype"], c["d"], c["H"], c["Hkv"], c["D"]
    ka = jax.random.split(k0, 4)
    wq = _frozen(_w(ka[0], (d, H * D), dt, d), c["block"])
    wk = _frozen(_w(ka[1], (d, Hkv * D), dt, d), c["block"])
    wv = _frozen(_w(ka[2], (d, Hkv * D), dt, d), c["block"])
    wo = _frozen(_w(ka[3], (H * D, d), dt, H * D), c["block"])
    q = lin(h, wq, lo["wq"]).reshape(B, S, H, D)
    kv = lambda w, n: jnp.repeat(lin(h, w, lo[n]).reshape(B, S, Hkv, D),
                                 H // Hkv, axis=2)
    o = ref_trunk.causal_attention(q, kv(wk, "wk"), kv(wv, "wv"), mm)
    return lin(o.reshape(B, S, H * D), wo, lo["wo"])


def selective_scan(delta, u, Bm, Cm, A):
    """h_t = exp(Δ_t A) h_{t-1} + Δ_t u_t B_t, y_t = <h_t, C_t>, one time
    step after another from a zero state."""
    def step(h, inp):
        d_t, u_t, b_t, c_t = inp
        h = jnp.exp(d_t[..., None] * A) * h + \
            (d_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("ben,bn->be", h, c_t)

    h0 = jnp.zeros((u.shape[0], *A.shape), F32)
    t_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, ys = jax.lax.scan(jax.checkpoint(step), h0,
                         tuple(map(t_major, (delta, u, Bm, Cm))))
    return jnp.moveaxis(ys, 0, 1)


def _mamba(h, k0, lo, c, mm, lin):
    dt, d, di, N, R, K = (c["dtype"], c["d"], c["di"], c["N"], c["R"],
                          c["K"])
    km = jax.random.split(k0, 6)
    q = lambda w: _frozen(w, c["block"])
    x1 = lin(h, q(_w(km[0], (d, di), dt, d)), lo["in_proj_x"])
    z = mm(h, q(_w(km[5], (d, di), dt, d)))
    conv_w = _w(km[1], (K, di), dt, K).astype(F32)
    conv_b = jax.random.normal(jax.random.fold_in(k0, 1), (di,), F32) * \
        (1.0 / jnp.sqrt(K))
    S = x1.shape[1]
    xp = jnp.pad(x1, ((0, 0), (K - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(xp[:, i:i + S] * conv_w[i] for i in range(K)) +
                     conv_b)
    proj = mm(xc, q(_w(km[2], (di, R + 2 * N), dt, di)))
    dt_r, Bm, Cm = _rms(proj[..., :R]), _rms(proj[..., R:R + N]), \
        _rms(proj[..., R + N:])
    delta = jax.nn.softplus(mm(dt_r, q(_w(km[3], (R, di), dt, R))) - 2.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=F32), (di, N))
    y = (selective_scan(delta, xc, Bm, Cm, A) + xc) * jax.nn.silu(z)
    return lin(y, q(_w(km[4], (di, d), dt, di)), lo["out_proj"])


def _moe(h, k4, c, mm):
    """(y, Switch load-balance term) of the expert layer."""
    B, S, d = h.shape
    dt, E, ff = c["dtype"], c["E"], c["ff"]
    x = h.reshape(B * S, d)
    ke = jax.random.split(k4, 4)
    router = jax.random.normal(ke[0], (d, E), F32) * (1.0 / jnp.sqrt(d))
    probs = jax.nn.softmax(mm(x, router), axis=-1)
    gates, ids = jax.lax.top_k(probs, c["k"])
    onehot = jax.nn.one_hot(ids, E, dtype=F32)          # (T, k, E)
    weight = jnp.einsum("tke,tk->et", onehot, gates)     # (E, T)
    aux = E * jnp.sum(onehot[:, 0].mean(0) * probs.mean(0))

    def expert(y, inp):
        k_e, w_e = inp
        kg, ku, kd = jax.random.split(k_e, 3)
        wg = _frozen(_w(kg, (d, ff), dt, d), c["block"])
        wu = _frozen(_w(ku, (d, ff), dt, d), c["block"])
        wd = _frozen(_w(kd, (ff, d), dt, ff), c["block"])
        o = mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)
        return y + w_e[:, None] * o, None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        (jax.random.split(ke[1], E), weight))
    return y.reshape(B, S, d), aux


def _mlp(h, k1, lo, c, lin):
    dt, d, ff = c["dtype"], c["d"], c["ff"]
    ks = jax.random.split(k1, 3)
    q = lambda w: _frozen(w, c["block"])
    wu, wd, wg = (q(_w(ks[0], (d, ff), dt, d)), q(_w(ks[1], (ff, d), dt, ff)),
                  q(_w(ks[2], (d, ff), dt, d)))
    g = jax.nn.silu(lin(h, wg, lo["wg"])) * lin(h, wu, lo["wu"])
    return lin(g, wd, lo["wd"])


SUBLAYERS = ("attn", "mamba", "mlp", "moe")
LORA_NAMES = ("wq", "wk", "wv", "wo", "in_proj_x", "out_proj", "wg", "wu",
              "wd")


def _sublayer(x, key, lo, *, kind, c, mm):
    """One residual sublayer: a mixer (attention or Mamba) or a
    feed-forward (dense SwiGLU or experts) of the layer whose key is
    ``key``. Returns (x, the Switch term)."""
    def lin(h, w, pair):
        return mm(h, w) + c["scale"] * mm(mm(h, pair["a"]), pair["b"])

    ks = jax.random.split(key, 8)
    h = _rms(x)
    zero = jnp.zeros((), F32)
    if kind == "attn":
        return x + _attention(h, ks[0], lo, c, mm, lin), zero
    if kind == "mamba":
        return x + _mamba(h, ks[0], lo, c, mm, lin), zero
    if kind == "mlp":
        return x + _mlp(h, ks[1], lo, c, lin), zero
    y, aux = _moe(h, ks[4], c, mm)
    return x + y, aux


def _sublayer_inputs(trainable, frozen, c):
    """Per sublayer (each layer's mixer, then its feed-forward): its kind,
    its layer's key, and its LoRA pairs, each name present in every
    sublayer (zeros where the sublayer has no such projection), so the
    sublayers scan as one sequence."""
    d, qd, kvd, di, ff, r = (c["d"], c["H"] * c["D"], c["Hkv"] * c["D"],
                             c["di"], c["ff"], c["r"])
    shapes = dict(wq=(d, qd), wk=(d, kvd), wv=(d, kvd), wo=(qd, d),
                  in_proj_x=(d, di), out_proj=(di, d), wg=(d, ff),
                  wu=(d, ff), wd=(ff, d))
    kinds, keys, los = [], [], []
    for i in range(c["L"]):
        j, p = i % c["period"], i // c["period"]
        lo = jax.tree.map(lambda l: l[p], trainable["lora"][j])
        for kind in c["kinds"][j]:
            kinds.append(SUBLAYERS.index(kind))
            keys.append(frozen["keys"][i])
            los.append({n: lo[n] if n in lo else
                        {"a": jnp.zeros((shapes[n][0], r), F32),
                         "b": jnp.zeros((r, shapes[n][1]), F32)}
                        for n in LORA_NAMES})
    stack = lambda *ls: jnp.stack(ls)
    return (jnp.asarray(kinds, jnp.int32), jnp.stack(keys),
            jax.tree.map(stack, *los))


def loss_fn(trainable, frozen, tokens, labels, config: dict,
            mode: str = "f32"):
    """Cross-entropy over the whole vocabulary plus 0.01 x the Switch
    terms of the expert layers. The sublayers run as one scan, each kind
    compiled once (``lax.switch``) and rematerialized in the backward."""
    c = sizes(config)
    mm = ref_trunk._mm(mode)
    x = frozen["embed"][tokens]
    B, S, d = x.shape
    branches = [lambda x, key, lo, kind=kind: _sublayer(
        x, key, lo, kind=kind, c=c, mm=mm) for kind in SUBLAYERS]

    @jax.checkpoint
    def body(x, inp):
        kind, key, lo = inp
        return jax.lax.switch(kind, branches, x, key, lo)

    x, aux = jax.lax.scan(body, x, _sublayer_inputs(trainable, frozen, c))
    aux = aux.sum()
    x = _rms(x)
    a = trainable["adapter"]
    Ha = c["Ha"]
    q, k, v = (mm(x, a[n]).reshape(B, S, Ha, d // Ha)
               for n in ("wq", "wk", "wv"))
    x = x + mm(ref_trunk.causal_attention(q, k, v, mm).reshape(B, S, d),
               a["wo"])
    h = jax.nn.relu(mm(x, a["w1"]) + a["b1"])
    x = x + mm(h, a["w2"]) + a["b2"]
    logits = mm(x, frozen["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + 0.01 * aux


def make_step(config: dict, *, lr: float, mode: str = "f32",
              half: bool = False):
    """A jitted ``(trainable, opt, frozen, tokens) -> (trainable, opt,
    loss, clipped gradient)``; ``tokens`` holds ``seq + 1`` ids a row.
    ``half`` keeps only the first half of each row's tokens (a planted
    fault: half of the batch's tokens left out)."""
    @jax.jit
    def step(tr, opt, frozen, toks):
        if half:
            toks = toks[:, :toks.shape[1] // 2 + 1]
        loss, g = jax.value_and_grad(loss_fn)(
            tr, frozen, toks[:, :-1], toks[:, 1:], config, mode)
        tr, opt, gc = ref_clip.adam_step(g, opt, tr, lr=lr)
        return tr, opt, loss, gc
    return step
