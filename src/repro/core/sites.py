"""Activation-site tagging.

Every offloadable activation in the model zoo is tagged with
``jax.ad_checkpoint.checkpoint_name``.  These names are the JAX analogue of
the paper's cross-iteration tensor identity: the policy generator selects
*sites*, the executor turns the selected sites into a
``save_and_offload_only_these_names`` remat policy, and the fuzzy matcher
(§6.1) re-associates policy entries with sites after the traced program
changes.

Under ``lax.scan`` over layers a site denotes the *stacked* per-layer
activation (one buffer per scan step); in unrolled mode sites carry an
``l{i}/`` prefix for per-layer granularity.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

# The canonical site vocabulary.  Order matters: it is also the one-hot bit
# assignment used by the integer fuzzy matcher (Appendix A adaptation).
OFFLOAD_SITES: Tuple[str, ...] = (
    "embed_out",      # token embedding output
    "ln_in",          # pre-norm input (residual stream snapshot)
    "qkv_proj",       # fused qkv projection output
    "attn_ctx",       # attention context (pre out-proj)
    "attn_out",       # attention block output
    "cross_kv",       # encoder / image KV (enc-dec + VLM)
    "cross_ctx",      # cross-attention context
    "ffn_pre",        # gate/up projection output
    "ffn_act",        # post-activation
    "ffn_out",        # down projection output
    "resid_mid",      # residual after attention
    "resid_post",     # residual after mlp (layer output / scan carry)
    "router_logits",  # MoE router scores
    "moe_dispatch",   # gathered expert inputs
    "moe_act",        # expert hidden activations
    "moe_out",        # combined expert outputs
    "ssm_in",         # mamba in-projection output
    "ssm_conv",       # post-conv activation
    "ssm_gate",       # gate branch
    "ssm_state",      # SSD chunk states
    "ssm_out",        # mamba block output
    "final_norm",
)
SITE_INDEX = {s: i for i, s in enumerate(OFFLOAD_SITES)}


class _Ctx(threading.local):
    def __init__(self):
        self.prefix = ""
        self.hook = None


_CTX = _Ctx()


@contextlib.contextmanager
def site_prefix(prefix: str):
    """Per-layer prefixing for unrolled (fine-grained) mode."""
    prev = _CTX.prefix
    _CTX.prefix = prefix
    try:
        yield
    finally:
        _CTX.prefix = prev


@contextlib.contextmanager
def site_hook(fn):
    """Route every tagged value through ``fn(site, x) -> x`` while the
    block runs (the pipelined layer stack captures residuals with it and
    hands them back in the backward pass)."""
    prev = _CTX.hook
    _CTX.hook = fn
    try:
        yield
    finally:
        _CTX.hook = prev


def tag(x, site: str):
    """Name ``x`` as an offloadable site.  The ``name`` equation sits in
    the named scope ``offload.<site>``; it lowers to no HLO op, and JAX
    gives the offload copies it derives a source info of their own."""
    assert site in SITE_INDEX, f"unknown site {site!r}"
    with jax.named_scope(f"offload.{site}"):
        x = checkpoint_name(x, _CTX.prefix + site)
    return x if _CTX.hook is None else _CTX.hook(site, x)


def base_site(name: str) -> str:
    """Strip any l{i}/ prefix back to the canonical site."""
    return name.rsplit("/", 1)[-1]
