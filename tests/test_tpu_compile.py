"""Compile rehearsal: every Pallas kernel of the main path, at the shapes
the chip runs, compiled for a described (not attached) TPU v5e chip.

Nothing runs; the TPU compiler either emits the Mosaic kernel
(``tpu_custom_call``) or refuses it, exactly as it would on the chip —
block alignment, unsupported vector ops and VMEM limits all surface
here. The topology is described inside a module-scoped fixture (never at
import), so every pytest-xdist worker collects the same tests and only
the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import quant as qlib
from repro.kernels import blockwise_quant as bq_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import lora_matmul as lm_k
from repro.kernels import quant_matmul as qm_k

M_TRAIN = 8 * 77          # batch 8 x CLIP's 77-token text context
RANK = 8
QBLOCK = 64               # the FL path's quant block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from a
    # persistent cache without the chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _qspec(K, N, bits, mode, sharding):
    qt = qlib.qtensor_specs((K, N), jnp.float32, bits=bits, block=QBLOCK,
                            mode=mode)
    return qlib.QTensor(q=_spec(qt.q.shape, qt.q.dtype, sharding),
                        scales=_spec(qt.scales.shape, jnp.float32,
                                     sharding),
                        bits=qt.bits, mode=qt.mode, block=qt.block,
                        out_dtype=qt.out_dtype, orig_shape=qt.orig_shape)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("N", [768, 3072])
@pytest.mark.parametrize("kernel", ["lora_matmul", "quant_matmul_t"])
def test_nf4_lora_kernels_compile(kernel, N, one_chip):
    K = 768
    qt = _qspec(K, N, 4, "nf4", one_chip)
    if kernel == "lora_matmul":
        _assert_kernel(
            lambda x, q, a, b: lm_k.lora_matmul(x, q, a, b, scale=2.0),
            _spec((M_TRAIN, K), jnp.bfloat16, one_chip), qt,
            _spec((K, RANK), jnp.float32, one_chip),
            _spec((RANK, N), jnp.float32, one_chip))
    else:
        _assert_kernel(lm_k.quant_matmul_t,
                       _spec((M_TRAIN, N), jnp.float32, one_chip), qt)


@pytest.mark.parametrize("bits,mode", [(4, "nf4"), (8, "linear")])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_lora_matmul_routes_compile_as_lora_matmul(dtype, bits, mode,
                                                    one_chip):
    """Both routes of the fused forward (three bf16 passes for a bf16 x,
    HIGHEST for f32) compile for v5e at the trunk's widest projection,
    and the instruction keeps the name the roofline reader matches."""
    K, N = 768, 3072
    compiled = jax.jit(
        lambda x, q, a, b: lm_k.lora_matmul(x, q, a, b, scale=2.0)).lower(
        _spec((M_TRAIN, K), dtype, one_chip),
        _qspec(K, N, bits, mode, one_chip),
        _spec((K, RANK), jnp.float32, one_chip),
        _spec((RANK, N), jnp.float32, one_chip)).compile()
    names = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert [n.rpartition(".")[0] or n for n in names] == ["lora_matmul"]


@pytest.mark.parametrize("bits,mode,K", [(8, "linear", 768),
                                         (4, "linear", 768),
                                         (4, "nf4", 768),
                                         (4, "linear", 64)])
def test_quant_matmul_compiles(bits, mode, K, one_chip):
    M = M_TRAIN if K > 64 else 16
    _assert_kernel(qm_k.quant_matmul,
                   _spec((M, K), jnp.float32, one_chip),
                   _qspec(K, K, bits, mode, one_chip))


@pytest.mark.parametrize("bits", [8, 4])
def test_blockwise_quant_compiles(bits, one_chip):
    _assert_kernel(
        lambda x: bq_k.blockwise_quant(x, bits=bits, block=QBLOCK),
        _spec((768, 768), jnp.float32, one_chip))


@pytest.mark.parametrize("S", [77, 512])
def test_flash_attention_compiles(S, one_chip):
    q = _spec((4, S, 12, 64), jnp.bfloat16, one_chip)
    _assert_kernel(
        lambda q, k, v: fa_k.flash_attention(q, k, v, causal=True),
        q, q, q)


def test_flash_attention_backward_compiles(one_chip):
    """The trainer differentiates attention: forward, dq and dk/dv
    kernels all compile at the CLIP text shape."""
    q = _spec((8, 77, 12, 64), jnp.bfloat16, one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(fa_k.flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_sharded_cohort_round_compiles(topo, monkeypatch):
    """The qlora cohort round sharded over a 2x2 v5e's four chips, with
    the compiled Pallas path the chip takes: Mosaic calls cannot be
    partitioned automatically, so the round must hand each device its
    own cohort rows (shard_map) for the adapter's attention kernel."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import clip as clip_lib
    from repro.data.synthetic import class_tokens, make_dataset
    from repro.fl import client as client_lib
    from repro.fl import cohort as cohort_lib
    from repro.fl.strategies import STRATEGIES
    from repro.kernels import ops as kops
    strat = STRATEGIES["qlora_nogan"]
    ccfg = clip_lib.CLIPConfig()
    frozen = clip_lib.init_clip(jax.random.PRNGKey(0), ccfg)
    data = make_dataset("pacs", n_per_class=4, seed=0)
    spec = data["spec"]
    class_emb = clip_lib.text_embedding(frozen, ccfg, jnp.asarray(
        class_tokens(spec, np.arange(spec.n_classes))))
    clients = [client_lib.Client(
        cid=i, images=data["images"][i::4], labels=data["labels"][i::4],
        n_classes=spec.n_classes, strategy=strat) for i in range(4)]
    eng = cohort_lib.CohortEngine(
        frozen=frozen, ccfg=ccfg, class_emb=class_emb, clients=clients,
        cfg=cohort_lib.CohortConfig(strategy=strat, local_steps=2,
                                    batch_size=8, lr=3e-3, donate=False))
    # steer the engine onto the described chips: the round program reads
    # its mesh and shard count from these two attributes
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    eng.cfg = dataclasses.replace(eng.cfg, mesh=mesh)
    eng.shards = 4
    monkeypatch.setattr(kops, "_use_pallas", lambda: True)
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def spec_of(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    tr = client_lib.init_trainable(jax.random.PRNGKey(1), ccfg, strat)
    idx = eng._sample_idx(jax.random.PRNGKey(2), eng.lens, 2)
    args = (jax.tree.map(lambda x: spec_of(x, rep), tr),
            spec_of(idx, rows), spec_of(eng.pool_staged, rows),
            spec_of(eng.pool_labs, rows), spec_of(eng.weights, rep),
            jax.tree.map(lambda x: spec_of(x, rep), eng.frozen),
            spec_of(eng.class_emb, rep))
    _assert_kernel(eng._build_round(), *args)


def test_trunk_step_kernels_keep_names_under_phase_scopes(one_chip,
                                                          monkeypatch):
    """The trunk's train step with the Pallas path and remat: the
    kernels keep the instruction names the benchmark's roofline readers
    match, and each sits under the phase scope its pass opens (forward,
    the remat recompute, the backward's transpose)."""
    import re

    from repro.configs import get_config
    from repro.core import optim
    from repro.kernels import ops as kops
    from repro.models import build_model
    monkeypatch.setattr(kops, "_use_pallas", lambda: True)
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg = get_config("clip-b32").replace(
        n_layers=2, d_model=256, n_heads=2, n_kv_heads=2, head_dim=128,
        d_ff=512, vocab_size=512, quant_bits=4, quant_mode="nf4",
        quant_block=QBLOCK, lora_rank=RANK, dtype="bfloat16",
        trainable_dtype="float32", adapter_heads=2, remat=True,
        seq_shard=False)
    model = build_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                            tree)

    tok = _spec((2, 128), jnp.int32, one_chip)
    batch = {"tokens": tok, "labels": tok,
             "mask": _spec((2, 128), jnp.float32, one_chip)}
    kops.reset_kernel_traces()
    text = jax.jit(model.train_step).lower(
        on_chip(params["frozen"]), on_chip(params["trainable"]),
        on_chip(jax.eval_shape(optim.adam_init, params["trainable"])),
        batch).compile().as_text()
    calls = {}
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%")
            path = re.search(r'op_name="([^"]*)"', line).group(1)
            calls.setdefault(name.rpartition(".")[0], []).append(path)
    assert set(calls) == {"lora_matmul", "quant_matmul_t",
                          "flash_attention"}
    # the bf16 residual stream takes the forward's three-pass route
    assert kops.KERNEL_TRACES.get("lora_matmul_pallas_bf16x3", 0) > 0

    def where(path):
        if "/rematted_computation/" in path:
            return "remat"
        if "/transpose(jvp(forward))/" in path:
            return "backward"
        assert "/jvp(forward)/" in path, path
        return "forward"

    assert {where(p) for p in calls["lora_matmul"]} == {"forward", "remat"}
    assert {where(p) for p in calls["quant_matmul_t"]} == {"backward"}
    assert {where(p) for p in calls["flash_attention"]} == \
        {"forward", "remat", "backward"}


# -- Jamba2-Mini: the cell's kernels and the whole step -----------------
JAMBA_S, JAMBA_DI, JAMBA_N = 2048, 8192, 16     # tokens, d_inner, d_state
JAMBA_ROWS = 8192          # round_up(2,048 x 2 + 16 x 255, 256) grouped rows


def _stacked_qspec(E, K, N, sharding):
    qt = qlib.qtensor_specs((E, K, N), jnp.bfloat16, bits=4, block=QBLOCK,
                            mode="nf4")
    return qlib.QTensor(q=_spec(qt.q.shape, qt.q.dtype, sharding),
                        scales=_spec(qt.scales.shape, jnp.float32,
                                     sharding),
                        bits=qt.bits, mode=qt.mode, block=qt.block,
                        out_dtype=qt.out_dtype, orig_shape=qt.orig_shape)


def _custom_calls(text):
    import re
    calls = {}
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%")
            m = re.search(r'op_name="([^"]*)"', line)
            calls.setdefault(name.rpartition(".")[0] or name, []).append(
                m.group(1) if m else "")
    return calls


@pytest.mark.parametrize("K,N", [(4096, 14336), (14336, 4096)])
@pytest.mark.parametrize("kernel", ["moe_gmm_fwd", "moe_gmm_dx"])
def test_moe_gmm_compiles_at_the_cell_shapes(kernel, K, N, one_chip):
    """The grouped NF4 expert matmul and its dx twin at Jamba2-Mini's
    expert widths (16 experts, 8,192 grouped rows), each one instruction
    under its own name."""
    from repro.kernels import moe_gmm as gk
    fn, cols = (gk.moe_gmm, K) if kernel == "moe_gmm_fwd" else \
        (gk.moe_gmm_t, N)
    text = jax.jit(fn).lower(
        _spec((JAMBA_ROWS, cols), jnp.bfloat16, one_chip),
        _stacked_qspec(16, K, N, one_chip),
        _spec((JAMBA_ROWS // 256,), jnp.int32, one_chip)).compile().as_text()
    assert list(_custom_calls(text)) == [kernel]


@pytest.mark.parametrize("kernel", ["ssm_scan_fwd", "ssm_scan_bwd"])
def test_selective_scan_compiles_at_the_cell_shapes(kernel, one_chip):
    """The selective scan's forward and backward kernels at one
    2,048-token sequence, d_inner 8,192, d_state 16."""
    from repro.kernels import selective_scan as sk
    f32 = lambda *sh: _spec(sh, jnp.float32, one_chip)
    ins = (f32(1, JAMBA_S, JAMBA_DI), f32(1, JAMBA_S, JAMBA_DI),
           f32(1, JAMBA_S, JAMBA_N), f32(1, JAMBA_S, JAMBA_N),
           f32(JAMBA_DI, JAMBA_N))
    if kernel == "ssm_scan_fwd":
        text = jax.jit(sk.scan_fwd).lower(*ins).compile().as_text()
    else:
        text = jax.jit(sk.scan_bwd).lower(
            *ins, f32(1, JAMBA_S // 128, JAMBA_N, JAMBA_DI),
            f32(1, JAMBA_S, JAMBA_DI),
            f32(1, JAMBA_DI, JAMBA_N)).compile().as_text()
    assert list(_custom_calls(text)) == [kernel]


def test_jamba_step_fits_and_keeps_kernel_names(one_chip, monkeypatch):
    """The cell's whole train step (Jamba2-Mini's 8-layer period at
    published widths, NF4, 1 x 2,048 tokens) with the Pallas path the
    chip takes: the compiler plans it under the chip's 16 GB; every
    kernel keeps the instruction name its roofline reader matches and
    sits under its block's scope; the counters show the scan's backward
    kernel and both grouped matmuls were taken (no autodiff of the
    chunked scan, no dequantized expert stack)."""
    from repro.configs import get_config
    from repro.core import optim
    from repro.kernels import ops as kops
    from repro.models import build_model
    monkeypatch.setattr(kops, "_use_pallas", lambda: True)
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg = get_config("jamba2-mini").replace(
        n_layers=8, quant_bits=4, quant_mode="nf4", quant_block=QBLOCK,
        lora_rank=16, lora_alpha=32.0, dtype="bfloat16",
        trainable_dtype="float32", adapter_heads=32, remat=True)
    model = build_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                            tree)

    tok = _spec((1, JAMBA_S), jnp.int32, one_chip)
    batch = {"tokens": tok, "labels": tok,
             "mask": _spec((1, JAMBA_S), jnp.float32, one_chip)}
    kops.reset_kernel_traces()
    compiled = jax.jit(model.train_step, donate_argnums=(1, 2)).lower(
        on_chip(params["frozen"]), on_chip(params["trainable"]),
        on_chip(jax.eval_shape(optim.adam_init, params["trainable"])),
        batch).compile()
    mem = compiled.memory_analysis()
    planned = mem.argument_size_in_bytes + mem.temp_size_in_bytes + \
        mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert planned < 16e9, planned
    calls = _custom_calls(compiled.as_text())
    assert set(calls) == {"lora_matmul", "quant_matmul_t", "flash_attention",
                          "ssm_scan_fwd", "ssm_scan_bwd", "moe_gmm_fwd",
                          "moe_gmm_dx"}
    for name in ("ssm_scan_pallas_bwd", "moe_gmm_pallas", "moe_gmm_t_pallas"):
        assert kops.KERNEL_TRACES.get(name, 0) > 0, name
    assert not any(k.endswith("_ref") for k in kops.KERNEL_TRACES)
    assert all("/mamba/" in p for n in ("ssm_scan_fwd", "ssm_scan_bwd")
               for p in calls[n])
    assert all("/moe/experts/" in p for n in ("moe_gmm_fwd", "moe_gmm_dx")
               for p in calls[n])
    assert any("/transpose(jvp(forward))/" in p for p in calls["moe_gmm_dx"])
