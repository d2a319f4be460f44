"""Minimal pure-JAX optimizers (no optax in this environment).

Used by FL clients (LoRA/adapter fine-tuning), the GAN, and the examples.
Optimizer state is a pytree mirroring the param tree, so it shards the same
way the params do under pjit.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def adam_specs(param_specs) -> AdamState:
    """ShapeDtypeStruct AdamState mirroring a spec tree (dry-run)."""
    z = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), param_specs)
    return AdamState(jax.ShapeDtypeStruct((), jnp.int32), z,
                     jax.tree.map(lambda s: s, z))


def adam_init(params) -> AdamState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return AdamState(jnp.zeros((), jnp.int32), zeros,
                     jax.tree.map(jnp.copy, zeros))


@jax.named_scope("adam")
def adam_update(grads, state: AdamState, params, *, lr, b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.0, grad_clip=0.0):
    """Returns (new_params, new_state). ``lr`` may be a float or a
    ``step -> lr`` schedule callable. Its ops sit in the ``adam`` scope,
    the grad clip included."""
    step = state.step + 1
    if grad_clip:
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    lr_t = lr(step) if callable(lr) else lr
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                      state.mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(
        g.astype(jnp.float32)), state.nu, grads)
    mu_hat_scale = 1.0 / (1 - b1 ** step.astype(jnp.float32))
    nu_hat_scale = 1.0 / (1 - b2 ** step.astype(jnp.float32))

    def upd(p, m, v):
        u = (m * mu_hat_scale) / (jnp.sqrt(v * nu_hat_scale) + eps)
        if weight_decay:
            u = u + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr_t * u).astype(p.dtype)

    new_params = jax.tree.map(upd, params, mu, nu)
    return new_params, AdamState(step, mu, nu)


def adam_scan(grad_fn, params, state: AdamState, xs, *, lr, b1=0.9,
              b2=0.999, eps=1e-8, weight_decay=0.0, grad_clip=0.0,
              unroll=1, active=None):
    """Fused local-training loop: one ``adam_update`` per leading element
    of ``xs``, inside a single ``lax.scan`` — the scan-friendly form used
    by the cohort engine and the CLIP pretraining loop, so a whole
    optimisation run is one XLA program (jit/donation-friendly, and the
    ``(params, state)`` carry buffers are reused in place on device).

    ``grad_fn(params, x) -> (grads, aux)``; returns
    ``(params, state, aux_stacked)`` where each adam_update step matches
    the Python-loop semantics of calling ``adam_update`` per batch.

    ``active`` — optional per-step bool vector (same leading length as
    ``xs``). Steps with ``active[t] == False`` leave params and optimizer
    state (moments *and* step counter) untouched, so a scan of static
    length S with the first ``n`` steps active is bit-identical to a
    Python loop of ``n`` adam_update calls. This is how the cohort engine
    runs clients with heterogeneous local-step counts inside one
    fixed-shape program; aux is still emitted for masked steps (evaluated
    on the frozen params) — callers index the last *active* entry.
    """
    masked = active is not None

    def body(carry, x):
        p, s = carry
        if masked:
            x, live = x
        g, aux = grad_fn(p, x)
        p2, s2 = adam_update(g, s, p, lr=lr, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay,
                             grad_clip=grad_clip)
        if masked:
            p2 = jax.tree.map(lambda a, b: jnp.where(live, a, b), p2, p)
            s2 = jax.tree.map(lambda a, b: jnp.where(live, a, b), s2, s)
        return (p2, s2), aux

    (params, state), aux = jax.lax.scan(
        body, (params, state), (xs, active) if masked else xs,
        unroll=unroll)
    return params, state, aux


def step_mask(n_steps, length: int):
    """Canonical ``active`` mask for the masked scans: the first
    ``n_steps`` of ``length`` scan steps live, the tail no-ops.
    ``n_steps`` may be a traced scalar (the cohort engine passes
    per-client counts under vmap). This is the one definition of
    "cut at step s" shared by the fused engines, the chaos layer's
    partial-work recovery, and the recovery property tests — cutting a
    run at ``s`` via this mask is bitwise running exactly ``s`` steps
    (params, both Adam moments, and the step counter)."""
    return jnp.arange(length) < n_steps


def sgd_update(grads, params, *, lr):
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)
                      ).astype(p.dtype), params, grads)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def cosine_schedule(base_lr: float, warmup: int, total: int) -> Callable:
    def sched(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
        warm = base_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)
    return sched
