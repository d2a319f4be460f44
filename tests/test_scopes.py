"""The phase scopes the round program and the trunk's train step open,
read back from the compiled programs' op names, and the host spans the
program runtime opens at its counters."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import clip as clip_lib
from repro.core import optim
from repro.data.synthetic import class_tokens, make_dataset
from repro.fl import client as client_lib
from repro.fl import cohort as cohort_lib
from repro.fl import runtime as runtime_lib
from repro.fl.strategies import STRATEGIES
from repro.models import build_model

PHASE_SCOPES = ("forward", "adam", "uplink", "fedavg")
# instructions that hold or route values rather than compute them
NON_COMPUTE = ("parameter", "constant", "tuple", "get-tuple-element",
               "while", "conditional", "call", "bitcast", "copy")
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_FUSED = re.compile(r"fusion\(.*?calls=%([\w.\-]+)")


def _bare(component):
    while component.endswith(")") and "(" in component:
        component = component[component.index("(") + 1:-1]
    return component


def _components(path):
    return {_bare(c) for c in path.split("/")}


def _op_paths(text):
    """(opcode, op-name path or None) of each compute instruction that
    runs as an op of its own: those inside a fusion's body are left out,
    as the fusion carries its root's name."""
    fused, comp, out = set(_FUSED.findall(text)), None, []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        if comp in fused:
            continue
        m = _INSTR.match(line)
        if m is None or m.group(1) in NON_COMPUTE:
            continue
        p = _OP_NAME.search(line)
        out.append((m.group(1), p.group(1) if p else None))
    return out


def _under_phase(path):
    comps = path.split("/")
    return any(c.startswith("transpose(") for c in comps) or \
        bool(_components(path) & set(PHASE_SCOPES))


@pytest.fixture(scope="module")
def round_text():
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
    rt = runtime_lib.ProgramRuntime()
    eng = cohort_lib.CohortEngine(
        frozen=frozen, ccfg=ccfg, class_emb=class_emb, clients=clients,
        cfg=cohort_lib.CohortConfig(strategy=strat, local_steps=2,
                                    batch_size=8, lr=3e-3, donate=False),
        runtime=rt)
    tr = client_lib.init_trainable(jax.random.PRNGKey(1), ccfg, strat)
    idx = eng._sample_idx(jax.random.PRNGKey(2), eng.lens, 2)
    args = (tr, idx, eng.pool_staged, eng.pool_labs, eng.weights,
            eng.frozen, eng.class_emb)
    exe = rt.compile("full_round", eng._build_round, args,
                     static_key=eng._static_key)
    return exe.as_text()


@pytest.fixture(scope="module")
def step_text():
    cfg = get_reduced("clip-b32").replace(quant_bits=4, quant_mode="nf4",
                                          quant_block=64)
    assert cfg.remat
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tr = params["trainable"]
    toks = jnp.zeros((2, 17), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
             "mask": jnp.ones((2, 16), jnp.float32)}
    return jax.jit(model.train_step).lower(
        params["frozen"], tr, optim.adam_init(tr), batch).compile().as_text()


def test_round_opens_every_phase_scope_and_its_kind(round_text):
    paths = [p for _, p in _op_paths(round_text) if p]
    comps = set().union(*map(_components, paths))
    for scope in PHASE_SCOPES + ("full_round", "attn", "mlp"):
        assert scope in comps, scope
    assert any("/transpose(jvp(forward))/" in p for p in paths)
    assert all("/full_round/" in p for p in paths
               if p.startswith("jit("))
    # the kind scope wraps the program and keeps its name
    assert round_text.startswith("HloModule jit_round_fn,")


def test_round_ops_almost_all_under_a_phase(round_text):
    # ops XLA makes itself may carry no name (the CPU's split reductions)
    named = [p for _, p in _op_paths(round_text) if p]
    assert len(named) > 100
    outside = [p for p in named if not _under_phase(p)]
    assert len(outside) <= 0.1 * len(named), sorted(set(outside))


def test_round_has_no_remat(round_text):
    assert "rematted_computation" not in round_text


def test_step_opens_forward_adam_and_remat(step_text):
    paths = [p for _, p in _op_paths(step_text) if p]
    comps = set().union(*map(_components, paths))
    for scope in ("forward", "adam", "rematted_computation", "attn",
                  "mlp"):
        assert scope in comps, scope
    assert any(p.startswith("jit(train_step)/jvp(forward)/") for p in paths)
    assert any(p.startswith("jit(train_step)/transpose(jvp(forward))/")
               for p in paths)
    assert any(p.startswith("jit(train_step)/adam/") for p in paths)
    # the recompute sits inside the backward's transpose
    assert all("/transpose(jvp(forward))/" in p for p in paths
               if "rematted_computation" in p and p.startswith("jit("))


def test_jamba_step_opens_the_block_scopes():
    """Jamba's period stack: the Mamba mixer sits in ``mamba``, the
    expert feed-forward in ``moe`` with its ``router``, ``dispatch``,
    ``experts`` and ``combine`` parts, beside the trunk's ``attn`` and
    ``mlp``; each position is rematerialized on its own."""
    cfg = get_reduced("jamba2-mini").replace(quant_bits=4, quant_mode="nf4",
                                             quant_block=64)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tr = params["trainable"]
    toks = jnp.zeros((1, 17), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
             "mask": jnp.ones((1, 16), jnp.float32)}
    text = jax.jit(model.train_step).lower(
        params["frozen"], tr, optim.adam_init(tr), batch).compile().as_text()
    paths = [p for _, p in _op_paths(text) if p]
    comps = set().union(*map(_components, paths))
    for scope in ("forward", "adam", "rematted_computation", "attn", "mlp",
                  "mamba", "moe", "router", "dispatch", "experts",
                  "combine"):
        assert scope in comps, scope
    for part in ("router", "dispatch", "experts", "combine"):
        assert all(f"moe/{part}/" in p for p in paths
                   if part in _components(p)), part


def test_runtime_spans_at_its_counters(monkeypatch):
    opened = []
    monkeypatch.setattr(runtime_lib, "span",
                        lambda name: opened.append(name) or
                        jax.profiler.TraceAnnotation(name))
    rt = runtime_lib.ProgramRuntime()
    x = jnp.ones(4)
    h = rt.dispatch("tiny", lambda: lambda a: a * 2, (x,))
    rt.dispatch("tiny", lambda: lambda a: a * 2, (x,)).result()
    h.result()
    rt.sync(x, tag="flush")
    assert opened == ["runtime.dispatch:tiny", "runtime.compile:tiny",
                      "runtime.dispatch:tiny",
                      "runtime.sync:handle_wait:tiny",
                      "runtime.sync:handle_wait:tiny",
                      "runtime.sync:flush"]
    assert runtime_lib.SYNC_TRACES["handle_wait:tiny"] >= 2


def test_kind_scope_keeps_the_function_name():
    def round_fn(a):
        return a + 1
    f = runtime_lib.scoped("full_round", round_fn)
    assert f.__name__ == "round_fn"
    text = jax.jit(f).lower(jnp.ones(2)).as_text(debug_info=True)
    assert "full_round" in text
