"""Host offload pipelined across a scanned layer stack (paper §5.3, §6).

Under ``jax.checkpoint(policy=save_and_offload_only_these_names(...))``
XLA issues and awaits each layer's host transfers inside that layer's scan
iteration, and the layer's matmuls fall outside that window, so the host
link runs while nothing computes (PERF.md §5).  :func:`offloaded_scan`
moves the same residuals itself, one layer apart — the paper's deferred
swap-out completion and swap-in pre-trigger:

* forward: layer ``i`` runs plainly; the values of its offloaded sites stay
  in HBM in the scan carry, and their store to pinned host is issued at the
  top of iteration ``i+1``, under layer ``i+1``'s compute, into slot
  ``i+1`` of the stacked host buffer.  Iteration 0 stores the carry's
  zeros into slot 0; after the loop the last layer's values take slot 0,
  while the head and loss compute.
* backward (``custom_vjp``): slot 0 (the last layer) is fetched before the
  reverse loop; the iteration for layer ``i`` first starts fetching slot
  ``i`` (layer ``i-1``) and carries it to the next iteration, then runs
  layer ``i``'s backward on values already in HBM.  Iteration 0's fetch
  (slot 0 again) is not used.

The residuals are the ones the checkpoint policy keeps: the tagged values
of the offload and save sites that the layer's backward reads, found once
per trace by dead-code elimination of that backward.  Saved sites stay in
HBM as the scan's stacked outputs, as under ``jax.checkpoint``; everything
else is recomputed.  Only where offloaded values live, and when they move,
differs from the checkpoint path; the host buffer holds as many layers.
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax._src.interpreters.partial_eval import dce_jaxpr

from repro.core.executor import OffloadSites
from repro.core.sites import site_hook

HOST, DEVICE = jax.memory.Space.Host, jax.memory.Space.Device

# layer(x, layer_params, consts) -> (x, aux)
Layer = Callable


@jax.custom_jvp
def _inject(value, saved):
    """``saved`` in place of ``value``, differentiated as ``value``: the
    backward reads the residual and recomputes nothing upstream of it."""
    return saved


@_inject.defjvp
def _inject_jvp(primals, tangents):
    return primals[1], tangents[0]


class _Plan(NamedTuple):
    """Where each captured value goes.  Capture 0 is the layer input, the
    rest are the layer's tagged values of the policy's sites, in call
    order; ``off`` go to pinned host, ``save`` stay in HBM (a capture in
    neither is not read by the backward).  ``avals`` has every capture's
    shape and dtype."""
    off: Tuple[int, ...]
    save: Tuple[int, ...]
    avals: Tuple[jax.ShapeDtypeStruct, ...]


def _capture(layer: Layer, keep, x, lp, consts):
    """Run the layer, returning its output and ``[(site, value)]``."""
    caps: List[Tuple[str, jax.Array]] = [("input", x)]

    def hook(site, v):
        if site in keep:
            caps.append((site, v))
        return v

    with site_hook(hook):
        out = layer(x, lp, consts)
    return out, caps


def _layer_vjp(layer: Layer, keep, vals: Dict[int, jax.Array], x, lp,
               consts, ct):
    """Layer backward with ``vals`` (capture index -> saved value) put in
    place of the tagged values it names."""
    count = itertools.count(1)

    def hook(site, v):
        if site not in keep:
            return v
        i = next(count)
        return _inject(v, vals[i]) if i in vals else v

    with site_hook(hook):
        _, vjp = jax.vjp(lambda x, lp: layer(x, lp, consts), x, lp)
    return vjp(ct)


def _plan(layer: Layer, sites: OffloadSites, x, lp, consts) -> _Plan:
    """Trace one layer abstractly: capture, then the backward against
    every capture, and keep the captures that backward reads."""
    keep = sites.offload | sites.save
    names: List[str] = []

    def forward(x, lp, c):
        out, caps = _capture(layer, keep, x, lp, c)
        names[:] = [s for s, _ in caps]
        return out, [v for _, v in caps]

    ct, avals = jax.eval_shape(forward, x, lp, consts)

    def backward(x, lp, c, vals, ct):
        return _layer_vjp(layer, keep, dict(enumerate(vals, 1)), x, lp, c, ct)

    jaxpr = jax.make_jaxpr(backward)(x, lp, consts, avals[1:], ct).jaxpr
    _, used = dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    start = 1 + len(jax.tree.leaves((lp, consts)))
    used = used[:1] + used[start:start + len(avals) - 1]
    off = tuple(i for i, s in enumerate(names)
                if used[i] and s in sites.offload)
    save = tuple(i for i in range(len(names)) if used[i] and i not in off)
    return _Plan(off, save, tuple(avals))


def _aval(t, shape=None):
    return jax.ShapeDtypeStruct(t.shape if shape is None else shape, t.dtype)


def offloaded_scan(layer: Layer, x, blocks, consts, sites: OffloadSites):
    """``lax.scan`` of ``layer`` over the stacked ``blocks``, returning the
    final ``x`` and the sum of the layers' float32 ``aux``; its gradient
    moves the offload sites' residuals to pinned host one layer behind the
    forward and fetches them one layer ahead of the backward."""
    keep = sites.offload | sites.save
    plan = _plan(layer, sites, _aval(x),
                 jax.tree.map(lambda t: _aval(t, t.shape[1:]), blocks),
                 jax.tree.map(_aval, consts))

    def scan_layers(x, blocks, consts):
        def body(carry, lp):
            y, a = layer(carry[0], lp, consts)
            return (y, carry[1] + a), None
        return jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                            blocks)[0]

    def fwd(x, blocks, consts):
        def body(carry, lp):
            x, aux, pend = carry
            stored = tuple(jax.device_put(v, HOST) for v in pend)
            (y, a), caps = _capture(layer, keep, x, lp, consts)
            off = tuple(caps[i][1] for i in plan.off)
            saved = tuple(caps[i][1] for i in plan.save)
            return (y, aux + a, off), (stored, saved)

        pend = tuple(jnp.zeros(plan.avals[i].shape, plan.avals[i].dtype)
                     for i in plan.off)
        (x, aux, last), (host, saved) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), pend), blocks)
        host = tuple(jax.lax.dynamic_update_index_in_dim(
            h, jax.device_put(v, HOST), 0, 0) for h, v in zip(host, last))
        return (x, aux), (blocks, consts, host, saved)

    def bwd(res, ct):
        blocks, consts, host, saved = res
        g_aux = ct[1]

        def body(carry, xs):
            g, cur = carry
            lp, prev, sav = xs
            nxt = tuple(jax.device_put(h, DEVICE) for h in prev)
            vals = dict(zip(plan.off, cur))
            vals.update(zip(plan.save, sav))
            x_in = vals.pop(0) if 0 in vals else jnp.zeros_like(g)
            g_x, g_lp = _layer_vjp(layer, keep, vals, x_in, lp, consts,
                                   (g, g_aux))
            return (g_x, nxt), g_lp

        last = tuple(jax.device_put(h[0], DEVICE) for h in host)
        (g_x, _), g_blocks = jax.lax.scan(
            body, (ct[0], last), (blocks, host, saved), reverse=True)
        return g_x, g_blocks, None

    f = jax.custom_vjp(scan_layers)
    f.defvjp(fwd, bwd)
    return f(x, blocks, consts)
