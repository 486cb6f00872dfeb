"""Scheme registry: config -> (uplink, downlink, aggregator) factories.

Every named FL scheme in the repo is a factory returning an
:class:`~repro.fl.engine.EngineSpec`, run by
``FLEngine(task, spec).run(...)``; adding a scheme is one entry here.  New
combinations that no seed loop could express -- e.g. an MRC uplink with a
sign-EF downlink -- are just a hand-rolled EngineSpec from the same channel
parts (see tests/test_channels.py).

BiCompFL variants (paper Algorithms 1 & 2, :func:`bicompfl_spec`):

* ``GR``          -- Alg. 1: global shared randomness; the federator *relays*
                     the clients' MRC indices, every client reconstructs the
                     identical global model (no extra compression noise).
* ``GR-Reconst``  -- the suboptimal ablation: the federator reconstructs the
                     global model and re-transmits it via a second MRC round
                     (common candidates -> all clients equal estimates).
* ``PR``          -- Alg. 2: private shared randomness only; per-client MRC
                     on the downlink; clients hold distinct estimates.
* ``PR-SplitDL``  -- PR, but the downlink sends each client only a disjoint
                     1/n slice of the blocks (downlink cost / n).

The uplink/downlink priors are the clients' latest global-model estimates
(theta_hat), exactly as the paper settles on (lambda = 1).
:func:`cfl_spec` is BiCompFL-GR in conventional FL: stochastic SignSGD
deltas conveyed by MRC against the uninformative prior p = 1/2.

Non-stochastic bi-directional baselines (paper Section 4,
:func:`baseline_spec`; the simplifications are in DESIGN.md section 5):

* fedavg         : dense 32-bit both directions.
* memsgd         : Stich et al. 2018  -- sign + EF uplink, dense downlink.
* doublesqueeze  : Tang et al. 2019  -- sign + EF uplink AND downlink.
* neolithic      : Huang et al. 2022 -- as doublesqueeze with R=2 compression
                   passes per direction (2 bits/param effective).
* cser           : Xie et al. 2020   -- sign + EF uplink, dense downlink,
                   periodic error reset (period 50) adds an amortized sync.
* liec           : Cheng et al. 2024 -- bidirectional sign with immediate
                   local error compensation + periodic averaging (period 50).
* m3             : Gruntkowska et al. 2024 -- TopK(d/n) + EF uplink; downlink
                   sends each client a *disjoint* 1/n model slice (dense);
                   clients hold diverging model estimates.
"""
from __future__ import annotations

from typing import Callable, Dict

from repro.core.blocks import (AdaptiveAllocation, AdaptiveAvgAllocation,
                               FixedAllocation)
from repro.core.quantizers import FLOAT_BITS
from repro.kernels.ops import mrc_logw_fn, segment_logw_fn
from .channels import (DenseChannel, IndexRelayDownlink, MRCAdaptiveChannel,
                       MRCBroadcastDownlink, MRCFixedChannel,
                       MRCPrivateDownlink, QuantizedMRCUplink, SignEFChannel,
                       SliceDownlink, SplitBlockDownlink, TopKEFChannel)
from .engine import EngineSpec, MeanDeltaAggregator, MeanModelAggregator

BICOMPFL_VARIANTS = ("GR", "GR-Reconst", "PR", "PR-SplitDL")


def bicompfl_spec(variant: str, *, allocation, n_is: int = 256, n_ul: int = 1,
                  n_dl: int = 1, chunk: int = 16, logw_fn=None,
                  participation: float = 1.0,
                  pallas_logw: bool = False,
                  segment_logw_pallas: bool = False) -> EngineSpec:
    """BiCompFL (probabilistic-mask) variants, paper Algorithms 1 & 2.

    ``n_dl`` must be resolved by the caller (the paper default is
    ``n_clients * n_ul``, which needs the cohort size).  ``pallas_logw``
    routes the fixed-block MRC importance-weight matvec through the Pallas
    ``mrc_weights`` kernel (``repro.kernels.ops.mrc_logw_fn``) on both
    directions; ``segment_logw_pallas`` is the adaptive-segment analog,
    routing the variable-block weight evaluation through the Pallas
    segment-logW kernel (``repro.kernels.ops.segment_logw_fn``) wherever a
    channel encodes against an adaptive plan.
    """
    if variant not in BICOMPFL_VARIANTS:
        raise ValueError(variant)
    if pallas_logw:
        if logw_fn is not None:
            raise ValueError("pass either logw_fn or pallas_logw, not both")
        logw_fn = mrc_logw_fn()
    seg_logw_fn = segment_logw_fn() if segment_logw_pallas else None
    if participation < 1.0 and variant != "PR":
        raise ValueError("partial participation requires private shared "
                         "randomness (the PR variant); GR needs all clients "
                         "to track the common candidate stream, and SplitDL "
                         "partitions the downlink across the full cohort")
    shared = variant.startswith("GR")
    adaptive = isinstance(allocation, AdaptiveAllocation)
    if adaptive:
        uplink = MRCAdaptiveChannel(n_is=n_is, n_samples=n_ul, shared=shared,
                                    seg_logw_fn=seg_logw_fn)
    else:
        uplink = MRCFixedChannel(n_is=n_is, n_samples=n_ul, shared=shared,
                                 chunk=chunk, logw_fn=logw_fn)
    if variant == "GR":
        downlink = IndexRelayDownlink(n_is=n_is, n_samples=n_ul)
    elif variant == "GR-Reconst":
        downlink = MRCBroadcastDownlink(n_is=n_is, n_samples=n_dl,
                                        chunk=chunk, logw_fn=logw_fn,
                                        seg_logw_fn=seg_logw_fn)
    elif variant == "PR":
        downlink = MRCPrivateDownlink(n_is=n_is, n_samples=n_dl,
                                      chunk=chunk, logw_fn=logw_fn,
                                      seg_logw_fn=seg_logw_fn)
    else:  # PR-SplitDL
        if adaptive:
            raise NotImplementedError("SplitDL is defined on fixed blocks")
        downlink = SplitBlockDownlink(n_is=n_is, n_samples=n_dl,
                                      chunk=chunk, logw_fn=logw_fn)
    return EngineSpec(uplink=uplink, downlink=downlink,
                      aggregator=MeanModelAggregator(), allocation=allocation,
                      participation=participation,
                      name=f"BiCompFL-{variant}")


def cfl_spec(*, n_is: int = 256, n_ul: int = 1, block_size: int = 16,
             server_lr: float = 1.0, chunk: int = 16, logw_fn=None) -> EngineSpec:
    """BiCompFL-GR-CFL: stochastic sign + MRC in conventional FL (Sec. 4)."""
    return EngineSpec(
        uplink=QuantizedMRCUplink(n_is=n_is, n_samples=n_ul, chunk=chunk,
                                  logw_fn=logw_fn),
        downlink=IndexRelayDownlink(n_is=n_is, n_samples=n_ul,
                                    side_info_bits=FLOAT_BITS),
        aggregator=MeanDeltaAggregator(server_lr),
        allocation=FixedAllocation(block_size),
        name="BiCompFL-GR-CFL")


# ---------------------------------------------------------------------------
# Non-stochastic baselines (paper Section 4); simplifications cf. DESIGN.md.
# ---------------------------------------------------------------------------


def _fedavg(n, d, lr, period):
    return EngineSpec(DenseChannel(), DenseChannel(), MeanDeltaAggregator(lr),
                      name="fedavg")


def _memsgd(n, d, lr, period):
    return EngineSpec(SignEFChannel(), DenseChannel(), MeanDeltaAggregator(lr),
                      name="memsgd")


def _doublesqueeze(n, d, lr, period):
    return EngineSpec(SignEFChannel(), SignEFChannel(), MeanDeltaAggregator(lr),
                      name="doublesqueeze")


def _neolithic(n, d, lr, period):
    return EngineSpec(SignEFChannel(passes=2), SignEFChannel(passes=2),
                      MeanDeltaAggregator(lr), name="neolithic")


def _cser(n, d, lr, period):
    return EngineSpec(SignEFChannel(), DenseChannel(), MeanDeltaAggregator(lr),
                      sync_period=period, name="cser")


def _liec(n, d, lr, period):
    return EngineSpec(SignEFChannel(), SignEFChannel(), MeanDeltaAggregator(lr),
                      sync_period=period, name="liec")


def _m3(n, d, lr, period):
    k = max(d // n, 1)  # one budget shared by the top-k uplink and the slices
    return EngineSpec(TopKEFChannel(k=k), SliceDownlink(k=k),
                      MeanDeltaAggregator(lr), name="m3")


BASELINE_BUILDERS: Dict[str, Callable[[int, int, float, int], EngineSpec]] = {
    "fedavg": _fedavg,
    "memsgd": _memsgd,
    "doublesqueeze": _doublesqueeze,
    "neolithic": _neolithic,
    "cser": _cser,
    "liec": _liec,
    "m3": _m3,
}

ALL_BASELINES = tuple(BASELINE_BUILDERS)


def baseline_spec(scheme: str, *, n: int, d: int, server_lr: float = 1.0,
                  reset_period: int = 50) -> EngineSpec:
    """Build a baseline EngineSpec; needs cohort size and model dimension
    (M3's top-k budget is d/n)."""
    key = scheme.lower()
    if key not in BASELINE_BUILDERS:
        raise ValueError(scheme)
    return BASELINE_BUILDERS[key](n, d, server_lr, reset_period)


def all_schemes(*, n: int, d: int, n_is: int = 16, block: int = 64,
                n_dl: int = None, server_lr: float = 1.0,
                reset_period: int = 50, include_adaptive: bool = False):
    """Every named scheme as ``(name, task_kind, spec_factory)`` triples.

    ``task_kind`` is "mask" (probabilistic-mask BiCompFL) or "delta"
    (conventional-FL: the baselines and BiCompFL-CFL).  Factories build a
    fresh spec per call.  Used by the fused-vs-host parity suite, the
    bit-accounting property suite and the round-throughput benchmark to
    enumerate the scheme matrix.

    ``include_adaptive=True`` appends the KL-driven allocations (the
    Isik-style segment codec on GR and PR, plus the paper's low-complexity
    Adaptive-Avg).  They are kept out of the default matrix because the
    fused engine runs them through *bucketed* plans -- equal to the host
    loop's exact plan only up to the bucketing bound, where the static
    schemes are bit-identical across engine paths.
    """
    ndl = n if n_dl is None else n_dl
    out = []
    for v in BICOMPFL_VARIANTS:
        out.append((f"bicompfl-{v.lower()}", "mask",
                    lambda v=v: bicompfl_spec(
                        v, allocation=FixedAllocation(block), n_is=n_is,
                        n_dl=ndl)))
    if include_adaptive:
        out.append(("bicompfl-gr-adaptive", "mask",
                    lambda: bicompfl_spec(
                        "GR", allocation=AdaptiveAllocation(n_is=n_is),
                        n_is=n_is, n_dl=ndl)))
        out.append(("bicompfl-pr-adaptive", "mask",
                    lambda: bicompfl_spec(
                        "PR", allocation=AdaptiveAllocation(n_is=n_is),
                        n_is=n_is, n_dl=ndl)))
        out.append(("bicompfl-gr-adaptive-avg", "mask",
                    lambda: bicompfl_spec(
                        "GR",
                        allocation=AdaptiveAvgAllocation(
                            n_is=n_is, min_block=block // 2,
                            max_block=8 * block),
                        n_is=n_is, n_dl=ndl)))
    out.append(("bicompfl-cfl", "delta",
                lambda: cfl_spec(n_is=n_is, block_size=16,
                                 server_lr=server_lr)))
    for s in ALL_BASELINES:
        out.append((s, "delta",
                    lambda s=s: baseline_spec(s, n=n, d=d,
                                              server_lr=server_lr,
                                              reset_period=reset_period)))
    return out


def wire_scheme_ids(*, n: int = 4, d: int = 64) -> Dict[str, int]:
    """Frame-header scheme ids for the full registry matrix.

    The engine stamps ``scheme_wire_id(spec.name)`` into every message of
    a wire-audited run; this enumerates the id of each registry scheme and
    fails loudly if two distinct spec names ever hash to the same 16-bit
    id (tests/test_wire.py pins the absence of collisions).
    """
    from repro.wire import scheme_wire_id
    ids: Dict[str, int] = {}
    by_id: Dict[int, str] = {}
    for _, _, factory in all_schemes(n=n, d=d, include_adaptive=True):
        name = factory().name
        wid = scheme_wire_id(name)
        if by_id.get(wid, name) != name:
            raise ValueError(
                f"wire scheme-id collision: {name!r} and {by_id[wid]!r} "
                f"both hash to {wid:#06x}")
        by_id[wid] = name
        ids[name] = wid
    return ids


def fault_matrix(*, n: int, d: int, n_is: int = 16, block: int = 64,
                 n_dl: int = None, reset_period: int = 2):
    """One scheme per uplink channel family, for fault-injection sweeps.

    The fault machinery's degradation paths split by channel *family*
    (MRC index streams, quantized-MRC deltas, sign-EF, top-k EF, dense),
    not by scheme, so the CI fault matrix and the robustness tests cover
    each family once instead of re-running the full registry:

    * ``bicompfl-pr``  -- MRC fixed-block uplink + client-specific
      (``downlink_recipients="active"``) MRC private downlink;
    * ``bicompfl-cfl`` -- quantized-MRC delta uplink, broadcast downlink;
    * ``doublesqueeze`` -- sign compression with error feedback on both
      links (EF rows must be carried for dropped clients);
    * ``m3``           -- top-k EF uplink (index payloads of varying
      width; excluded from uniform per-client wire-bit assertions);
    * ``fedavg``       -- dense float uplink, the no-compression control.

    Same ``(name, task_kind, factory)`` triples as :func:`all_schemes`.
    """
    ndl = n if n_dl is None else n_dl
    return [
        ("bicompfl-pr", "mask",
         lambda: bicompfl_spec("PR", allocation=FixedAllocation(block),
                               n_is=n_is, n_dl=ndl)),
        ("bicompfl-cfl", "delta",
         lambda: cfl_spec(n_is=n_is, block_size=16)),
        ("doublesqueeze", "delta",
         lambda: baseline_spec("doublesqueeze", n=n, d=d,
                               reset_period=reset_period)),
        ("m3", "delta",
         lambda: baseline_spec("m3", n=n, d=d, reset_period=reset_period)),
        ("fedavg", "delta",
         lambda: baseline_spec("fedavg", n=n, d=d,
                               reset_period=reset_period)),
    ]
