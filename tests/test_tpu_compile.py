"""Compile guards for one TPU v5e chip, described and not attached.

The TPU compiler installed beside JAX compiles for a ``v5e:2x2`` topology
that is only described, so what the chip's compiler would refuse (block
shapes off the (8, 128) tiling, VMEM overuse, a program that does not fit
HBM, host offload that cannot be lowered) fails here, at no chip time.
Nothing runs: these say nothing about values or times.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU library.  Keep every such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.common.config import ChameleonConfig, TrainConfig
from repro.core.executor import Executor, OffloadSites
from repro.distributed import steps as S
from repro.kernels.autotune.space import SPACES
from repro.kernels.flash_attention import kernel as FK
from repro.kernels.quant_offload import kernel as QK
from repro.kernels.ssd_scan import kernel as SK

GiB = 1 << 30
# memory_stats()["bytes_limit"] of one v5e chip ("TPU v5 lite"), 15.748 GiB
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compilation
    cache off: entries compiled for a described chip cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(fn, abstract args) for one kernel at the widths chip_smoke runs."""
    bf = jnp.bfloat16
    if name == "flash_attention_fwd":           # qwen1.5-0.5b, batch 2
        qkv = _sds(sh, (2, 16, 2048, 64), bf)
        return (lambda q, k, v: FK.flash_attention_fwd(
            q, k, v, causal=True, sm_scale=0.125), (qkv, qkv, qkv))
    if name == "flash_decode_fwd":              # batch 4 over 2048 keys
        kv = _sds(sh, (4, 16, 2048, 64), bf)
        return (lambda q, k, v, n: FK.flash_decode_fwd(
            q, k, v, n, sm_scale=0.125),
            (_sds(sh, (4, 16, 1, 64), bf), kv, kv,
             _sds(sh, (4,), jnp.int32)))
    if name == "ssd_scan_fwd":                  # mamba2-780m widths
        bn = _sds(sh, (1, 4096, 128), bf)
        return (lambda *a: SK.ssd_scan_fwd(*a, chunk=256),
                (_sds(sh, (1, 48, 4096, 64), bf), _sds(sh, (1, 48, 4096), bf),
                 _sds(sh, (48,), jnp.float32), bn, bn))
    kind, br = name.split(":")
    if kind == "quantize_fwd":
        return (lambda x: QK.quantize_fwd(x, block_rows=int(br)),
                (_sds(sh, (4096, 1024), bf),))
    return (lambda q, s: QK.dequantize_fwd(q, s, bf, block_rows=int(br)),
            (_sds(sh, (4096, 1024), jnp.int8),
             _sds(sh, (4096, 1), jnp.float32)))


KERNELS = (["flash_attention_fwd", "flash_decode_fwd", "ssd_scan_fwd"]
           + [f"{k}:{v['block_rows']}" for k, space in
              (("quantize_fwd", SPACES["quantize"]),
               ("dequantize_fwd", SPACES["dequantize"]))
              for v in space.variants])


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _qwen_state(one_chip):
    cfg = C.get_config("qwen1_5_0_5b")
    params, opt = S.abstract_train_state(cfg)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    return cfg, place(params), place(opt)


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_qwen_grad_step_offloads_to_host(one_chip):
    """qwen1.5-0.5b at published widths, 2 x 2048 tokens, every offload
    site offloaded: XLA must place the residuals in host memory, and the
    step must fit one chip beside the resident AdamW state."""
    cfg, params, opt = _qwen_state(one_chip)
    tcfg = TrainConfig()
    policy = Executor(ChameleonConfig()).conservative().to_jax()
    batch = {k: _sds(one_chip, (2, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = jax.jit(S.make_grad_step(cfg, tcfg, policy)).lower(
        params, batch, _sds(one_chip, (), jnp.float32)).compile()
    ma = compiled.memory_analysis()
    assert ma.host_temp_size_in_bytes > 0
    assert "S(5)" in compiled.as_text()         # pinned-host memory space
    device_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes + _nbytes(opt))
    assert device_bytes <= V5E_BYTES_LIMIT, device_bytes / GiB


def test_qwen_apply_step_donates_state(one_chip):
    """The trainer's AdamW update writes the whole new state (bf16 params
    and AdamW state) into the donated buffers of the old one."""
    cfg, params, opt = _qwen_state(one_chip)
    grads = jax.tree.map(lambda x: _sds(one_chip, x.shape, jnp.float32),
                         params)
    with pytest.warns(UserWarning, match="donated buffers were not usable"):
        compiled = S.jit_apply_step(cfg, TrainConfig()).lower(
            params, opt, grads).compile()   # f32 grads match no output
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias > 0
    # the device pads small buffers (the int32 step) to its alignment
    assert alias == pytest.approx(_nbytes(params) + _nbytes(opt), abs=4096)


_ASYNC = re.compile(r"\s(dynamic-(?:update-)?slice-(?:start|done))\((%[\w.\-]+)")


def _computations(hlo: str):
    """name -> instruction lines of each computation of an HLO module."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line)
    return comps


def _host_transfer_windows(hlo: str):
    """For each computation with pinned-host transfers: per transfer, the
    count of matmul-bearing instructions (a dot, a convolution, or a
    fusion that calls one) scheduled between its start and its done."""
    comps = _computations(hlo)
    memo = {}

    def matmul(line):
        if re.search(r"\s(convolution|dot)\(", line):
            return True
        m = re.search(r"\sfusion\(.*calls=(%[\w.\-]+)", line)
        if not m:
            return False
        if m.group(1) not in memo:
            memo[m.group(1)] = any(matmul(l) for l in comps.get(m.group(1), []))
        return memo[m.group(1)]

    windows = {}
    for name, lines in comps.items():
        starts, found = {}, []
        for i, line in enumerate(lines):
            m = _ASYNC.search(line)
            if m and m.group(1).endswith("start") and "S(5)" in line:
                starts[line.split(" = ")[0].strip().split()[-1]] = i
            elif m and m.group(2) in starts:
                s = starts[m.group(2)]
                found.append(sum(map(matmul, lines[s + 1:i])))
        if found:
            windows[name] = found
    return windows


def test_qwen_pipelined_offload_overlaps_compute(one_chip):
    """qwen1.5-0.5b at published widths, 2 x 2048 tokens, under the swap
    cell's Stable policy: the pipelined stack schedules a matmul between
    the start and the done of every pinned-host transfer, in the forward
    and backward loop bodies alike; it moves as many host bytes as the
    ``jax.checkpoint`` path and holds at most one more layer's residuals
    (77.6 MB) in HBM."""
    cfg, params, _ = _qwen_state(one_chip)
    batch = {k: _sds(one_chip, (2, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    sites = OffloadSites(
        frozenset({"ffn_act", "ffn_pre", "ln_in"}),
        frozenset({"attn_ctx", "attn_out", "embed_out", "final_norm",
                   "qkv_proj"}))
    compiled = {
        name: jax.jit(S.make_grad_step(cfg, TrainConfig(), pol)).lower(
            params, batch, _sds(one_chip, (), jnp.float32)).compile()
        for name, pol in (("pipelined", sites),
                          ("checkpoint", sites.checkpoint_policy))}
    hlo = compiled["pipelined"].as_text()
    windows = _host_transfer_windows(hlo)
    loops = set(re.findall(r"body=(%[\w.\-]+)", hlo))
    assert len(loops & set(windows)) >= 2, windows
    assert all(n >= 1 for w in windows.values() for n in w), windows
    ma, ref = (compiled[k].memory_analysis()
               for k in ("pipelined", "checkpoint"))
    assert ma.host_temp_size_in_bytes == ref.host_temp_size_in_bytes
    assert ma.host_temp_size_in_bytes / GiB == pytest.approx(1.734, abs=1e-3)
    assert ma.temp_size_in_bytes <= ref.temp_size_in_bytes + 0.08 * GiB
