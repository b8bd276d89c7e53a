"""Executor (paper §6): apply a generated SwapPolicy to the training program.

On TPU/XLA the application mechanism is a ``save_and_offload_only_these_names``
remat policy threaded into the model's scanned blocks and a re-``jit`` of the
step — the compile-time analogue of re-routing the dispatch stream.  XLA's
static schedule plays the role of the paper's custom recordStream: the
simulator's swap-out completion points become buffer release points that the
latency-hiding scheduler honors without host polling (§6.2); we additionally
donate input buffers so optimizer-state memory is reused in place.

An applied policy that offloads a site hands the model an
:class:`OffloadSites`: a checkpoint policy that also names its two site
sets, so the dense and MoE layer stacks can run the offload themselves,
pipelined across layers (``repro.models.pipelined``, the paper's swap-in
pre-trigger and deferred swap-out completion, §5.3).

``offload_mode="compressed"`` (beyond-paper, CSWAP-inspired) wraps offloaded
sites in an int8 quantize/dequantize pair so swapped tensors cross the host
link at half/quarter width — see ``repro.kernels.quant_offload``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, Iterable, Optional, Sequence, Set

import jax

from repro.common.config import ChameleonConfig
from repro.core.policy import SwapPolicy
from repro.core.profiler import ProfileData
from repro.core.sites import OFFLOAD_SITES

# Sites that are cheap to recompute from their saved neighbors (elementwise):
# the beyond-paper 3-way save/offload/remat decision drops these from the
# saved set when host bandwidth is the binding constraint.
CHEAP_RECOMPUTE_SITES: Set[str] = {"ffn_act", "ssm_gate", "ln_in"}


def jax_offload_policy(offload_sites: Iterable[str],
                       save_sites: Iterable[str]):
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=sorted(set(save_sites)),
        names_which_can_be_offloaded=sorted(set(offload_sites)),
        offload_src="device", offload_dst="pinned_host")


def jax_save_policy(save_sites: Iterable[str]):
    return jax.checkpoint_policies.save_only_these_names(
        *sorted(set(save_sites)))


@dataclass(frozen=True)
class OffloadSites:
    """What ``to_jax()`` hands the model for an applied policy that
    offloads: sites in ``offload`` go to pinned host, sites in ``save``
    stay in HBM, the rest are recomputed.  The dense and MoE stacks move
    those residuals themselves (``repro.models.pipelined``); every other
    stack applies ``checkpoint_policy``, ``jax_offload_policy`` of the two
    sets."""
    offload: FrozenSet[str]
    save: FrozenSet[str]
    checkpoint_policy: Callable = field(init=False, repr=False,
                                        compare=False)

    def __post_init__(self):
        object.__setattr__(self, "checkpoint_policy",
                           jax_offload_policy(self.offload, self.save))


@dataclass
class AppliedPolicy:
    swap: Optional[SwapPolicy]
    offload: Set[str]
    save: Set[str]
    remat: Set[str]
    fingerprint: str
    raw: bool = False    # save *everything* incl. untagged f32 temporaries
    # §5.4.2 feedback: tag -> simulator-promised swap-out completion op.
    # The execution path hands this to the transfer engine so HBM is freed
    # at the promised op (engine.advance_op) instead of at first reuse.
    release_plan: Dict[str, int] = field(default_factory=dict)

    @property
    def pipelined(self) -> bool:
        """The stack is handed an :class:`OffloadSites` (it offloads)."""
        return bool(self.offload) and not self.raw

    def to_jax(self):
        if self.raw:
            return None  # no checkpoint wrapper at all
        if self.pipelined:
            return OffloadSites(frozenset(self.offload), frozenset(self.save))
        return jax_save_policy(self.save)


class Executor:
    def __init__(self, cfg: ChameleonConfig):
        self.cfg = cfg

    def site_universe(self, prof: Optional[ProfileData]) -> Set[str]:
        if prof is None:
            return set(OFFLOAD_SITES)
        sites = {t.site for t in prof.candidates if t.site}
        return sites or set(OFFLOAD_SITES)

    def lower(self, swap: SwapPolicy, prof: ProfileData,
              remat_fallback: Optional[bool] = None) -> AppliedPolicy:
        """SwapPolicy (per-tensor decisions) -> site-level applied policy."""
        offload = swap.offload_sites(prof)
        universe = self.site_universe(prof)
        save = universe - offload
        remat: Set[str] = set()
        use_remat = (self.cfg.allow_remat_fallback
                     if remat_fallback is None else remat_fallback)
        if use_remat:
            remat = (save & CHEAP_RECOMPUTE_SITES)
            save -= remat
        fp = ("off=" + ",".join(sorted(offload))
              + "|save=" + ",".join(sorted(save)))
        plan = {SwapPolicy.entry_tag(e): e.swap_out_done_op
                for e in swap.entries if e.swap_out_done_op >= 0}
        return AppliedPolicy(swap, offload, save, remat, fp,
                             release_plan=plan)

    def bind_release_points(self, applied: AppliedPolicy, engine) -> int:
        """Hand the applied policy's release plan to the transfer engine
        (superseding any previous policy's): swap-outs tagged with a
        planned tensor carry ``release_op`` and are retired by
        ``engine.advance_op`` at the simulator-promised op."""
        engine.clear_planned_releases()
        for tag, op in applied.release_plan.items():
            engine.plan_release(tag, op)
        return len(applied.release_plan)

    def conservative(self, prof: Optional[ProfileData] = None) -> AppliedPolicy:
        """WarmUp-stage fallback: offload every candidate site (guaranteed
        fit analogue of passive swap; see core.oom for the targeted loop)."""
        universe = self.site_universe(prof)
        return AppliedPolicy(None, set(universe), set(), set(),
                             "warmup-offload-all")

    def baseline(self) -> AppliedPolicy:
        """PyTorch-equivalent no-swap baseline: every named activation site
        is saved in its stored dtype; elementwise internals (f32 upcasts of
        norms/rope/softmax) are recomputed in the backward — what fused
        autograd kernels do.  This is the program the profiler traces and
        the memory curve the MRL is built from (Fig 3)."""
        return AppliedPolicy(None, set(), set(OFFLOAD_SITES), set(),
                             "baseline-save-sites")

    def raw(self) -> AppliedPolicy:
        """Save-everything (no remat wrapper): upper bound on activation
        memory; reported in benches for contrast, never used as the paper
        baseline."""
        return AppliedPolicy(None, set(), set(OFFLOAD_SITES), set(),
                             "raw-save-everything", raw=True)
