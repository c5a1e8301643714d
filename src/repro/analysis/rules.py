"""The invariant rules R1–R6 (DESIGN.md §12).

Each rule is a pure function ``HotPath -> [Finding]`` registered in
``repro.analysis.core.RULES``. The underlying checkers are also exported
as plain functions over (jaxpr, meta...) so tests can drive them against
seeded-violation fixtures without building a full hot path.

  R1 resident-purity   zero slab pack/unpack copies in the resident step
  R2 dtype-policy      no unintended upcasts on the compute-tier path
  R3 host-sync         no callbacks/transfers inside hot jaxprs
  R4 donation          donated buffers actually input-output aliased
  R5 pallas-lint       BlockSpec VMEM budget, divisibility, coverage
  R6 collectives       no unexpected collectives in compiled HLO
"""
from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.analysis.core import Finding, Rule, register
from repro.analysis.jaxpr_walk import (eqn_locus, frame_in, invar_ids,
                                       iter_eqns, marked_walk, pallas_calls,
                                       var_marked)

#: R2: upcasts below this element count are scalar/control plumbing
#: (loss scalars, per-layer stats), not weight-path traffic.
DTYPE_MIN_ELEMS = 16384
#: R2: source paths whose casts are sanctioned by construction (the SR /
#: RTN compute casts live in the Pallas kernels and their jnp fallbacks).
DTYPE_WHITELIST = ("repro/kernels",)

#: R5: per-platform VMEM budget the BlockSpec working set must fit
#: (double-buffered). TPU v4/v5 cores carry 16 MiB of VMEM.
VMEM_LIMIT_BYTES = 16 * 2 ** 20
VMEM_WARN_FRAC = 0.9
#: (sublane, lane) tile the last two dims of a TPU block must respect
TPU_TILE = (8, 128)

HOST_SYNC_PRIMS = frozenset({
    "callback", "debug_callback", "debug_print", "infeed", "io_callback",
    "outfeed",
    "outside_call", "pure_callback",
})
TRANSFER_PRIMS = frozenset({"device_put"})


def _f(rule: str, severity: str, path: Any, locus: str,
       message: str) -> Finding:
    return Finding(rule=rule, severity=severity, path=path.name,
                   config=path.config, locus=locus, message=message)


# ------------------------------------------------------------------- R1 --
def resident_purity_findings(jaxpr: Any, rows: int,
                             compute_seeds: Iterable[int],
                             lanes: int = 512) -> List[Tuple[str, str]]:
    """(locus, message) pairs: fp32 (rows, lanes) slab concatenates (a
    per-step pack of master/moments) and slab slices NOT derived from the
    compute slab (a per-step unpack; forward reads OF the compute slab are
    the one sanctioned slice)."""
    shape = (int(rows), int(lanes))
    out: List[Tuple[str, str]] = []

    def visit(eqn, marked):
        name = eqn.primitive.name
        if name == "concatenate":
            av = eqn.outvars[0].aval
            if getattr(av, "shape", None) == shape \
                    and av.dtype == jnp.float32:
                out.append((eqn_locus(eqn),
                            "per-step slab PACK: fp32 "
                            f"{shape} concatenate in the step graph"))
        elif name == "slice":
            av = eqn.invars[0].aval
            if getattr(av, "shape", None) == shape \
                    and av.dtype == jnp.float32 \
                    and not var_marked(eqn.invars[0], marked):
                out.append((eqn_locus(eqn),
                            "per-step slab UNPACK: fp32 slice of a "
                            f"{shape} slab that is not the compute slab"))

    marked_walk(jaxpr, compute_seeds, visit)
    return out


def _check_r1(path: Any) -> List[Finding]:
    rows = path.meta.get("rows")
    if rows is None:
        return []
    seeds = invar_ids(path.jaxpr, path.meta.get("compute_slab", []))
    return [_f("R1", "error", path, locus, msg)
            for locus, msg in resident_purity_findings(path.jaxpr, rows,
                                                       seeds)]


# ------------------------------------------------------------------- R2 --
def dtype_policy_findings(jaxpr: Any, weight_seeds: Iterable[int],
                          min_elems: int = DTYPE_MIN_ELEMS,
                          whitelist: Sequence[str] = DTYPE_WHITELIST
                          ) -> List[Tuple[str, str]]:
    """(locus, message) pairs for widening float ``convert_element_type``
    equations whose operand is weight-derived (reachable from the weight
    invars through layout-only primitives): a silent promotion of the
    compute-tier path back to a wider dtype. Casts traced from whitelisted
    source paths (the kernels' own SR/RTN casts) are sanctioned."""
    out: List[Tuple[str, str]] = []

    def visit(eqn, marked):
        if eqn.primitive.name != "convert_element_type":
            return
        op = eqn.invars[0]
        if not var_marked(op, marked):
            return
        src, dst = op.aval.dtype, eqn.outvars[0].aval.dtype
        if not (jnp.issubdtype(src, jnp.floating)
                and jnp.issubdtype(dst, jnp.floating)):
            return
        if jnp.dtype(dst).itemsize <= jnp.dtype(src).itemsize:
            return
        size = 1
        for d in getattr(op.aval, "shape", ()):
            size *= d
        if size < min_elems:
            return
        if any(frame_in(eqn, frag) for frag in whitelist):
            return
        out.append((eqn_locus(eqn),
                    f"weight-derived upcast {jnp.dtype(src).name} -> "
                    f"{jnp.dtype(dst).name} of {size} elements on the "
                    "compute-tier path"))

    marked_walk(jaxpr, weight_seeds, visit)
    return out


def _check_r2(path: Any) -> List[Finding]:
    ranges = path.meta.get("weights", [])
    if not ranges:
        return []
    seeds = invar_ids(path.jaxpr, ranges)
    return [_f("R2", "error", path, locus, msg)
            for locus, msg in dtype_policy_findings(path.jaxpr, seeds)]


# ------------------------------------------------------------------- R3 --
def host_sync_findings(jaxpr: Any) -> List[Tuple[str, str, str]]:
    """(severity, locus, message) for host-synchronizing equations: any
    callback primitive is an error (a device->host round trip per step);
    an in-graph device_put of a weight-sized floating tensor is a transfer
    warning (small integer placements are trace-time constant metadata —
    e.g. the slab row-layer tables — and are ignored)."""
    out: List[Tuple[str, str, str]] = []
    for eqn in iter_eqns(jaxpr, enter_pallas=False):
        name = eqn.primitive.name
        if name in HOST_SYNC_PRIMS:
            out.append(("error", eqn_locus(eqn),
                        f"host callback `{name}` inside a hot jaxpr — "
                        "forces a device->host sync every step"))
        elif name in TRANSFER_PRIMS:
            av = eqn.outvars[0].aval
            size = 1
            for d in getattr(av, "shape", ()):
                size *= d
            if jnp.issubdtype(av.dtype, jnp.floating) \
                    and size >= DTYPE_MIN_ELEMS:
                out.append(("warn", eqn_locus(eqn),
                            f"in-graph `{name}` of {av.str_short()} — "
                            "implicit transfer/placement inside a hot "
                            "jaxpr"))
    return out


def _check_r3(path: Any) -> List[Finding]:
    return [_f("R3", sev, path, locus, msg)
            for sev, locus, msg in host_sync_findings(path.jaxpr)]


# ------------------------------------------------------------------- R4 --
_ALIAS_HEAD = re.compile(r"input_output_alias=\{")
_ALIAS_PARAM = re.compile(r"\(\s*(\d+)\s*,")


def aliased_params(hlo: str) -> List[int]:
    """Flat entry-parameter indices that are input-output aliased in
    compiled HLO text (the ``input_output_alias={ {out}: (param, ...) }``
    header attribute)."""
    m = _ALIAS_HEAD.search(hlo)
    if not m:
        return []
    depth, i = 1, m.end()
    while i < len(hlo) and depth:
        depth += {"{": 1, "}": -1}.get(hlo[i], 0)
        i += 1
    return [int(p) for p in _ALIAS_PARAM.findall(hlo[m.end():i - 1])]


def donation_findings(hlo: str, donated: Sequence[Tuple[int, int]]
                      ) -> List[Tuple[str, str, str]]:
    """(severity, locus, message): donated flat params that XLA did not
    alias. All-missing is an error (donation silently dropped — the state
    or cache is double-buffered every step); partial is a warning."""
    expect = {i for start, count in donated
              for i in range(start, start + count)}
    if not expect:
        return []
    got = set(aliased_params(hlo)) & expect
    missing = sorted(expect - got)
    if not missing:
        return []
    sev = "error" if not got else "warn"
    what = ("no donated buffer is aliased" if not got else
            f"{len(missing)}/{len(expect)} donated buffers not aliased")
    return [(sev, f"input_output_alias params {missing[:8]}",
             f"{what} — donated state is being copied, not reused")]


def _check_r4(path: Any) -> List[Finding]:
    donated = path.meta.get("donated", [])
    if not donated:
        return []
    hlo = path.hlo
    if hlo is None:
        return []
    return [_f("R4", sev, path, locus, msg)
            for sev, locus, msg in donation_findings(hlo, donated)]


# ------------------------------------------------------------------- R5 --
def _tpu_tiled(b: Any) -> bool:
    """Mosaic's block rule: each of the last two block dims is a multiple
    of the (sublane, lane) tile or the whole array dim."""
    return all(bd == ad or bd % t == 0 for bd, ad, t in
               zip(b.block_shape[-2:], b.array_shape[-2:], TPU_TILE))


def pallas_findings(jaxpr: Any,
                    vmem_limit: int = VMEM_LIMIT_BYTES
                    ) -> List[Tuple[str, str, str]]:
    """(severity, locus, message) per pallas_call: double-buffered
    BlockSpec working set vs the VMEM budget, block/array divisibility
    (a block extent that does not tile its array dim reads/writes a
    partial tile every grid step), the TPU (8, 128) block rule, and output
    grid coverage (grid x block
    must reach every output element — an undersized grid silently leaves
    output regions unwritten)."""
    out: List[Tuple[str, str, str]] = []
    for call in pallas_calls(jaxpr):
        vmem = sum(b.block_elems * jnp.dtype(b.dtype).itemsize * 2
                   for b in call.blocks)
        if vmem > vmem_limit:
            out.append(("error", call.locus,
                        f"BlockSpec working set ~{vmem / 2**20:.1f} MiB "
                        f"(double-buffered) exceeds the {vmem_limit//2**20}"
                        " MiB VMEM budget"))
        elif vmem > VMEM_WARN_FRAC * vmem_limit:
            out.append(("warn", call.locus,
                        f"BlockSpec working set ~{vmem / 2**20:.1f} MiB is "
                        f">{int(VMEM_WARN_FRAC*100)}% of the "
                        f"{vmem_limit//2**20} MiB VMEM budget"))
        for b in call.blocks:
            for bd, ad in zip(b.block_shape[-len(b.array_shape):],
                              b.array_shape):
                if 1 < bd < ad and ad % bd != 0:
                    out.append((
                        "error", call.locus,
                        f"block {b.block_shape} does not tile array "
                        f"{b.array_shape}: {ad} % {bd} != 0"))
                    break
            if len(b.array_shape) >= 2 and not _tpu_tiled(b):
                out.append((
                    "error", call.locus,
                    f"block {b.block_shape} over array {b.array_shape}: "
                    "the last two block dims must be multiples of "
                    f"{TPU_TILE} or equal the array dims (Mosaic refuses "
                    "it; interpret mode does not)"))
        for b in call.blocks:
            if not b.is_output:
                continue
            total = 1
            for d in b.array_shape:
                total *= d
            if call.grid_size * b.block_elems < total:
                out.append((
                    "error", call.locus,
                    f"grid {call.grid} x block {b.block_shape} covers "
                    f"{call.grid_size * b.block_elems} elements < output "
                    f"{b.array_shape} ({total}) — unwritten regions"))
    return out


def _check_r5(path: Any) -> List[Finding]:
    limit = path.meta.get("vmem_limit_bytes", VMEM_LIMIT_BYTES)
    return [_f("R5", sev, path, locus, msg)
            for sev, locus, msg in pallas_findings(path.jaxpr, limit)]


# ------------------------------------------------------------------- R6 --
def collective_findings(hlo: str,
                        allowance: Optional[Dict[str, float]] = None
                        ) -> List[Tuple[str, str, str]]:
    """(severity, locus, message) for trip-count-expanded collective
    traffic in compiled HLO beyond the path's allowance. Row-range-sharded
    slab sweeps only combine O(L) per-layer stats, so anything weight- or
    activation-sized (stray all-gathers from a bad sharding annotation)
    is a regression."""
    from repro.roofline.hlo_parse import collective_bytes
    allowance = allowance or {}
    out: List[Tuple[str, str, str]] = []
    for kind, nbytes in sorted(collective_bytes(hlo).items()):
        if nbytes > allowance.get(kind, 0.0):
            out.append(("error", f"hlo {kind}",
                        f"{nbytes / 2**20:.2f} MiB of {kind} traffic "
                        f"(allowance {allowance.get(kind, 0.0) / 2**20:.2f}"
                        " MiB) in the compiled hot path"))
    return out


def _check_r6(path: Any) -> List[Finding]:
    hlo = path.hlo
    if hlo is None:
        return []
    allowance = path.meta.get("collective_allowance", {})
    return [_f("R6", sev, path, locus, msg)
            for sev, locus, msg in collective_findings(hlo, allowance)]


# ------------------------------------------------------------ registry --
register(Rule(id="R1", title="resident-purity: zero per-step slab "
              "pack/unpack copies", kinds=("train",), needs="jaxpr",
              check=_check_r1))
register(Rule(id="R2", title="dtype-policy: no unintended upcasts on the "
              "compute-tier path", kinds=("train", "decode", "chunk",
                                          "admit", "infer"),
              needs="jaxpr", check=_check_r2))
register(Rule(id="R3", title="host-sync: no callbacks/transfers in hot "
              "jaxprs", kinds=("*",), needs="jaxpr", check=_check_r3))
register(Rule(id="R4", title="donation: donated buffers input-output "
              "aliased", kinds=("train", "decode", "chunk", "admit"),
              needs="compiled", check=_check_r4))
register(Rule(id="R5", title="pallas-lint: VMEM budget, divisibility, "
              "grid coverage", kinds=("*",), needs="jaxpr",
              check=_check_r5))
register(Rule(id="R6", title="collectives: no unexpected collective "
              "traffic", kinds=("train", "decode", "chunk", "admit",
                                "repack", "infer"),
              needs="compiled", check=_check_r6))
