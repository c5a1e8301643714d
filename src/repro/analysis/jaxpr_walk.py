"""Structured jaxpr traversal for the invariant linter (DESIGN.md §12).

Everything the rules in ``repro.analysis.rules`` need to inspect a jaxpr
without string matching:

  * ``iter_eqns`` — recursive equation iteration into every call-like
    sub-jaxpr (pjit, scan, while, cond, custom_vjp, remat, pallas_call),
  * ``eqn_locus`` / ``eqn_frame`` — the user-code source location an
    equation was traced from (for findings and provenance whitelists),
  * ``marked_walk`` — dataflow marking: which values derive from a seed
    set of inputs through layout-only primitives (the machinery behind
    the resident-purity and dtype-policy rules),
  * ``slab_copy_counts`` — (rows, 512) fp32 slab pack/unpack counting,
    the structured replacement for the hand-rolled test walkers,
  * ``pallas_calls`` — BlockSpec/grid introspection of every pallas_call
    equation (block shapes, backing array shapes, kernel name + source).
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Set, Tuple)

import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from jax._src import source_info_util as _siu  # source provenance

#: Primitives that move/view/re-type data without computing on it — the
#: propagation set for ``marked_walk``: a value is "derived from" a seed
#: exactly when every step between them is one of these.
LAYOUT_PRIMS = frozenset({
    "broadcast_in_dim", "concatenate", "convert_element_type", "copy",
    "dynamic_slice", "expand_dims", "gather", "rev", "reshape", "slice",
    "squeeze", "transpose",
})


def as_jaxpr(j: Any) -> Jaxpr:
    """ClosedJaxpr -> Jaxpr (identity on a Jaxpr)."""
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def is_literal(v: Any) -> bool:
    return hasattr(v, "val")


def sub_jaxprs(eqn: Any) -> Iterator[Jaxpr]:
    """Every jaxpr nested in an equation's params (pjit ``jaxpr``, scan
    bodies, cond ``branches`` lists, pallas_call kernels, ...)."""
    def walk(v: Any) -> Iterator[Jaxpr]:
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            yield as_jaxpr(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from walk(x)
    for v in eqn.params.values():
        yield from walk(v)


def iter_eqns(jaxpr: Any, *, enter_pallas: bool = True) -> Iterator[Any]:
    """Depth-first equation iteration over ``jaxpr`` and every sub-jaxpr.
    ``enter_pallas=False`` treats pallas_call kernels as opaque — the right
    mode for XLA-program-level rules (host sync, slab copies)."""
    stack: List[Jaxpr] = [as_jaxpr(jaxpr)]
    while stack:
        for eqn in stack.pop().eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call" and not enter_pallas:
                continue
            stack.extend(sub_jaxprs(eqn))


# ------------------------------------------------------------ provenance --
def eqn_frame(eqn: Any) -> Optional[Tuple[str, int]]:
    """(file_name, line) of the user frame an equation was traced from."""
    fr = _siu.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None
    return str(fr.file_name), int(fr.start_line)


def short_path(path: str, parts: int = 2) -> str:
    return "/".join(path.replace("\\", "/").split("/")[-parts:])


def eqn_locus(eqn: Any) -> str:
    """Human/JSON locus: ``primitive aval @ dir/file.py:line``."""
    try:
        aval = " " + eqn.outvars[0].aval.str_short()
    except Exception:
        aval = ""
    fr = eqn_frame(eqn)
    at = f" @ {short_path(fr[0])}:{fr[1]}" if fr else ""
    return f"{eqn.primitive.name}{aval}{at}"


def frame_in(eqn: Any, fragment: str) -> bool:
    """True when the equation's user frame lives under a path containing
    ``fragment`` — the provenance whitelist test (e.g. "repro/kernels")."""
    fr = eqn_frame(eqn)
    return fr is not None and fragment in fr[0].replace("\\", "/")


# -------------------------------------------------------- dataflow marks --
def _call_maps(eqn: Any) -> List[Tuple[Jaxpr, List[Any], bool]]:
    """(sub_jaxpr, outer var per sub invar (None = unmapped), outs_map)
    triples for a call-like equation. ``outs_map`` says whether the sub's
    outvars correspond positionally to the equation's outvars."""
    name = eqn.primitive.name
    params = eqn.params
    out: List[Tuple[Jaxpr, List[Any], bool]] = []
    if name == "cond":
        for br in params["branches"]:
            out.append((as_jaxpr(br), list(eqn.invars[1:]), True))
    elif name == "while":
        cn = int(params["cond_nconsts"])
        bn = int(params["body_nconsts"])
        carry = list(eqn.invars[cn + bn:])
        out.append((as_jaxpr(params["cond_jaxpr"]),
                    list(eqn.invars[:cn]) + carry, False))
        out.append((as_jaxpr(params["body_jaxpr"]),
                    list(eqn.invars[cn:cn + bn]) + carry, True))
    else:
        for sub in sub_jaxprs(eqn):
            n, m = len(sub.invars), len(eqn.invars)
            if n == m:                      # pjit, scan, closed_call, ...
                out.append((sub, list(eqn.invars), True))
            elif n < m:                     # leading consts on the eqn
                out.append((sub, list(eqn.invars[m - n:]), True))
            else:                           # leading consts on the sub
                pad: List[Any] = [None] * (n - m)
                out.append((sub, pad + list(eqn.invars), True))
    return out


def marked_walk(jaxpr: Any, seeds: Iterable[int],
                visit: Optional[Callable[[Any, Set[int]], None]] = None,
                *, layout: frozenset = LAYOUT_PRIMS) -> List[bool]:
    """Propagate a "derived from ``seeds`` through layout-only primitives"
    mark across ``jaxpr``, recursing into call-like sub-jaxprs with
    positional argument mapping. ``seeds`` holds ``id()``s of this jaxpr's
    vars (usually invars — see ``invar_ids``). ``visit(eqn, marked)``,
    when given, runs for every equation at every depth with the enclosing
    jaxpr's live mark set (query operands with ``var_marked``).
    pallas_call bodies are opaque: their outputs are never marked.
    Returns per-outvar markedness of the top-level jaxpr."""

    def run(jx: Jaxpr, mk: Set[int]) -> List[bool]:
        for eqn in jx.eqns:
            if visit is not None:
                visit(eqn, mk)
            name = eqn.primitive.name
            if name == "pallas_call":
                continue
            maps = _call_maps(eqn)
            if maps:
                out_m = [False] * len(eqn.outvars)
                for sub, argv, outs_map in maps:
                    sm = {id(sv) for sv, ov in zip(sub.invars, argv)
                          if ov is not None and not is_literal(ov)
                          and id(ov) in mk}
                    sub_out = run(sub, sm)
                    if outs_map and len(sub.outvars) == len(eqn.outvars):
                        out_m = [a or b
                                 for a, b in zip(out_m, sub_out)]
                for ov, m in zip(eqn.outvars, out_m):
                    if m:
                        mk.add(id(ov))
            elif name in layout:
                ins = [v for v in eqn.invars if not is_literal(v)]
                if ins and all(id(v) in mk for v in ins):
                    for ov in eqn.outvars:
                        mk.add(id(ov))
        return [(not is_literal(v)) and id(v) in mk for v in jx.outvars]

    return run(as_jaxpr(jaxpr), set(seeds))


def var_marked(v: Any, marked: Set[int]) -> bool:
    return (not is_literal(v)) and id(v) in marked


def invar_ids(jaxpr: Any,
              ranges: Sequence[Tuple[int, int]]) -> Set[int]:
    """Seed set for ``marked_walk``: ``id()``s of the flat invars covered
    by ``[(start, count), ...]`` index ranges."""
    invars = as_jaxpr(jaxpr).invars
    out: Set[int] = set()
    for start, count in ranges:
        for v in invars[start:start + count]:
            out.add(id(v))
    return out


# ------------------------------------------------------------ slab copies --
def slab_copy_counts(jaxpr: Any, rows: int,
                     lanes: int = 512) -> Dict[str, int]:
    """fp32 ``(rows, lanes)`` ``concatenate`` (= slab pack) and
    slice-of-slab (= unpack) equation counts across every sub-jaxpr — the
    structured form of the old test-local ``_slab_copy_counts`` walker.
    The resident train step must show ``{"concatenate": 0, "slice": 0}``
    modulo slices that R1 separately proves are compute-slab reads."""
    counts = {"concatenate": 0, "slice": 0}
    shape = (int(rows), int(lanes))
    for eqn in iter_eqns(jaxpr, enter_pallas=False):
        name = eqn.primitive.name
        if name == "concatenate":
            av = eqn.outvars[0].aval
            if getattr(av, "shape", None) == shape \
                    and av.dtype == jnp.float32:
                counts["concatenate"] += 1
        elif name == "slice":
            av = eqn.invars[0].aval
            if getattr(av, "shape", None) == shape \
                    and av.dtype == jnp.float32:
                counts["slice"] += 1
    return counts


# ------------------------------------------------------- pallas BlockSpec --
@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One operand's block mapping of a pallas_call."""
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: Any
    is_output: bool

    @property
    def block_elems(self) -> int:
        n = 1
        for d in self.block_shape:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class PallasCallInfo:
    """BlockSpec geometry of one pallas_call equation."""
    name: str                       # kernel function name
    src: str                        # "path/to/kernel.py:line"
    grid: Tuple[int, ...]
    blocks: Tuple[BlockInfo, ...]

    @property
    def grid_size(self) -> int:
        n = 1
        for g in self.grid:
            n *= g
        return n

    @property
    def locus(self) -> str:
        return f"pallas_call {self.name} @ {short_path(self.src)}"


def _block_dim(d: Any) -> int:
    """Block dims are ``Blocked(block_size=n)`` objects (or ints); ``None``
    and ``Squeezed()`` mark a squeezed dim (extent 1)."""
    d = getattr(d, "block_size", d)
    return 1 if d is None or not isinstance(d, int) else d


def pallas_calls(jaxpr: Any) -> List[PallasCallInfo]:
    """Every pallas_call in ``jaxpr`` (recursively) with its grid and
    per-operand block geometry."""
    out: List[PallasCallInfo] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        bms = list(gm.block_mappings)
        n_out = int(getattr(gm, "num_outputs", len(eqn.outvars)))
        blocks = []
        for i, bm in enumerate(bms):
            asd = bm.array_aval
            blocks.append(BlockInfo(
                block_shape=tuple(_block_dim(d) for d in bm.block_shape),
                array_shape=tuple(int(s) for s in asd.shape),
                dtype=asd.dtype,
                is_output=i >= len(bms) - n_out))
        nsi = str(eqn.params.get("name_and_src_info", ""))
        name, _, src = nsi.partition(" at ")
        out.append(PallasCallInfo(name=name or "<pallas>", src=src,
                                  grid=tuple(int(g) for g in gm.grid),
                                  blocks=tuple(blocks)))
    return out
