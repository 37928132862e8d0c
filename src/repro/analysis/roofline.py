"""Three-term roofline from a compiled XLA module (no hardware needed).

    compute term    = HLO_FLOPs   / peak_FLOP/s        (per chip)
    memory term     = HLO_bytes   / HBM_bw             (per chip)
    collective term = coll_bytes  / (links * link_bw)  (per chip)

``compiled.cost_analysis()`` on the SPMD-partitioned module reports
*per-device* FLOPs/bytes (verified empirically: an unsharded matmul reports
its exact global FLOPs; a sharded one reports global/n_devices). Collective
bytes are not in cost_analysis, so we parse the optimized HLO text and sum
``max(result, operands)`` bytes per collective instruction.

IMPORTANT: XLA counts a ``while`` body ONCE, so the dry-run lowers with
layers UNROLLED; recurrent archs (xlstm sLSTM scan over sequence) still
contain while loops — their cells carry an explicit note + analytic
correction factor in EXPERIMENTS.md.

Hardware constants: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (we credit 3 usable link-pairs per chip on a 2-D torus
slice and report the assumption).
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_LINK_BW = 50e9         # bytes/s per link
ICI_LINKS = 3              # usable link-pairs credited per chip

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    """Sum bytes over every typed shape literal in ``txt``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind max(result, operand) bytes summed over instances.

    Parses lines like
      ``%x = bf16[4096,512] all-reduce(bf16[4096,512] %y), ...``.
    Bytes are per-device (the module is the per-device program).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*((?:\([^)]*\)|\S+))\s+(" + "|".join(_COLLECTIVES)
                      + r")(?:-start|-done)?\(", stripped)
        if not m:
            continue
        kind = m.group(2)
        if "-done(" in stripped:
            continue  # paired with -start; count once
        result_bytes = _shape_bytes(m.group(1))
        args = stripped[m.end():]
        operand_bytes = _shape_bytes(args.split(", replica_groups")[0]
                                     if ", replica_groups" in args else args)
        out[kind] += max(result_bytes, operand_bytes)
        counts[kind] += 1
    out_nonzero = {k: v for k, v in out.items() if v}
    return {"bytes_by_kind": out_nonzero,
            "counts": {k: v for k, v in counts.items() if v},
            "total_bytes": sum(out.values())}


@dataclasses.dataclass
class Roofline:
    flops: float               # per device
    hbm_bytes: float           # per device
    coll_bytes: float          # per device
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops_total: float   # 6ND-style whole-step useful FLOPs
    useful_ratio: float        # model_flops / (flops * n_devices)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(cost: dict, hlo_text: str, n_devices: int,
            model_flops_total: float,
            coll_bytes_override: float | None = None) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    if coll_bytes_override is not None:
        coll = dict(coll)
        coll["total_bytes"] = coll_bytes_override
    t_c = flops / PEAK_FLOPS
    t_m = hbm / HBM_BW
    t_x = coll["total_bytes"] / (ICI_LINKS * ICI_LINK_BW)
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bn = max(terms, key=terms.get)
    useful = model_flops_total / max(flops * n_devices, 1.0)
    return Roofline(flops=flops, hbm_bytes=hbm,
                    coll_bytes=float(coll["total_bytes"]),
                    t_compute=t_c, t_memory=t_m, t_collective=t_x,
                    bottleneck=bn, model_flops_total=model_flops_total,
                    useful_ratio=useful)


def model_flops(cfg, shape_kind: str, seq: int, batch: int) -> float:
    """6*N_active*D for training, 2*N_active*D for inference forward;
    decode counts one token per sequence in the batch."""
    n = cfg.active_param_count()
    if shape_kind == "train":
        return 6.0 * n * seq * batch
    if shape_kind == "prefill":
        return 2.0 * n * seq * batch
    return 2.0 * n * batch      # decode: one token per sequence


# ---------------------------------------------------------------------------
# Limb-op roofline for the encrypted ADMM stack (repro.obs RunReports)
# ---------------------------------------------------------------------------

LIMB_BITS = 16                 # the kernels' limb width (bigint.py)
# Assumed peak 16-bit limb-multiply throughput for the reference device.
# This is an ORDER-OF-MAGNITUDE anchor, not a measured number: one modern
# CPU core retiring ~1 vectorized 16x16->32 multiply-accumulate per cycle
# at ~4 GHz. Override per call when a measured peak is available.
PEAK_LIMB_MULS_PER_S = 4e9
GAMMA2_EXP_BITS = 20           # typical Gamma_2 exponent width (~log2 Delta)


def _active_reduce_impl() -> str:
    import os
    return os.environ.get("REPRO_REDUCE_IMPL", "montgomery")


def ladder_mulmods(method: str, exp_bits: int,
                   reduce_impl: str = "barrett") -> float:
    """Executed mulmods for one ModExp under the active ladder schedule.

    * ``binary`` — the constant-time Algorithm-2 ladder executes BOTH the
      squaring and the selected multiply every bit: ``2/bit``;
    * ``win4`` — 4 squarings + 1 oblivious table select per 4-bit window
      plus the 15-mulmod power table: ``1.25/bit + 15``;
    * ``fixed`` — the batch-shared host-known-exponent ladder
      (``ops.modexp_fixed``): same window schedule as win4 over the
      exponent's TRUE bit-length (leading zero windows trimmed host-side).

    ``reduce_impl="montgomery"`` adds the 2 domain enter/leave
    REDC-equivalents (amortized over the ladder, but executed).
    """
    if method == "binary":
        n = 2.0 * exp_bits
    elif method in ("win4", "fixed"):
        n = 1.25 * exp_bits + 15.0 if exp_bits > 0 else 0.0
    else:
        raise ValueError(f"unknown modexp method {method!r}")
    if reduce_impl == "montgomery" and n > 0:
        n += 2.0
    return n


def limb_ops(ops: dict, key_bits: int,
             exp_bits: int = GAMMA2_EXP_BITS,
             method: str | None = None,
             reduce_impl: str | None = None) -> dict:
    """16-bit limb-multiplications implied by an OpCounter ``ops`` dict.

    ``ops`` is the RunReport ``"ops"`` section: ``{phase: {op: count}}``.
    Ciphertexts live mod n^2, i.e. ``L = ceil(2*key_bits / 16)`` limbs.
    Schoolbook costs per op, priced by the ACTIVE ladder schedule
    (``method`` defaults to win4 and ``reduce_impl`` to
    ``$REPRO_REDUCE_IMPL``/montgomery — the same defaults
    ``kernels/ops.py`` resolves, so the accounting tracks what actually
    ran):

    * ``mulmod``  — one LxL product: ``L^2``;
    * ``modexp``  — :func:`ladder_mulmods`(method, exp_bits) ``* L^2``;
    * ``enc``/``dec`` — one full-width exponentiation (r^n, resp. c^phi)
      with a key-constant exponent, so the fixed-window schedule applies:
      :func:`ladder_mulmods`("fixed", key_bits) ``* L^2``.
    """
    method = method or "win4"
    reduce_impl = reduce_impl or _active_reduce_impl()
    L = max(1, -(-2 * key_bits // LIMB_BITS))
    totals: dict[str, int] = {}
    for per_phase in ops.values():
        for op, n in per_phase.items():
            totals[op] = totals.get(op, 0) + int(n)
    key_exp = ladder_mulmods("fixed", key_bits, reduce_impl)
    per_op = {
        "modexp": ladder_mulmods(method, exp_bits, reduce_impl) * L * L,
        "mulmod": float(L * L),
        "enc": key_exp * L * L,
        "dec": key_exp * L * L,
    }
    by_op = {op: totals.get(op, 0) * per_op[op]
             for op in per_op if totals.get(op)}
    return {"key_bits": key_bits, "limbs": L, "exp_bits": exp_bits,
            "method": method, "reduce_impl": reduce_impl,
            "by_op": by_op, "limb_muls": sum(by_op.values())}


def achieved_vs_peak(ops: dict, key_bits: int, seconds: float,
                     peak: float = PEAK_LIMB_MULS_PER_S,
                     exp_bits: int = GAMMA2_EXP_BITS,
                     method: str | None = None,
                     reduce_impl: str | None = None) -> dict:
    """Achieved limb-mul rate over ``seconds`` vs the assumed device peak.

    ``seconds`` may be wall or virtual time — a RunReport built on the
    simulated clock reports utilization *of the modeled device*, which is
    the number the paper's speedup-ratio evaluation compares.
    """
    lo = limb_ops(ops, key_bits, exp_bits=exp_bits, method=method,
                  reduce_impl=reduce_impl)
    rate = lo["limb_muls"] / seconds if seconds > 0 else 0.0
    lo.update(seconds=seconds, peak_limb_muls_per_s=peak,
              limb_muls_per_s=rate,
              fraction_of_peak=rate / peak if peak > 0 else 0.0)
    return lo
