"""repro.analysis: seeded-violation fixtures per rule + clean sweeps.

Each rule R1-R6 must demonstrably FAIL on a fixture built to violate it
(with the finding pointing at the right locus) and pass on the adjacent
clean variant — otherwise a lint that never fires proves nothing. The
slow sweep then asserts the real hot paths are clean on every config.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import (get_rules, invar_ids, kernel_paths,
                            pallas_calls, run_analysis, slab_copy_counts,
                            validate_schema)
from repro.analysis.report import ANALYSIS_SCHEMA, build_report
from repro.analysis.rules import (aliased_params, collective_findings,
                                  donation_findings, dtype_policy_findings,
                                  host_sync_findings, pallas_findings,
                                  resident_purity_findings)

ROWS, LANES = 64, 512


# ------------------------------------------------------------------- R1 --
def _packed_step(master, moment):
    # the shape of the sin: re-packing master+moment into a slab and
    # slicing a freshly-built slab back apart, once per step
    slab = jnp.concatenate([master, moment], axis=0)
    part = jax.lax.slice(slab, (0, 0), (ROWS // 2, LANES))
    return jnp.sum(part * slab[ROWS // 2:, :].sum())


def test_r1_seeded_pack_and_unpack_fire():
    a = jnp.zeros((ROWS // 2, LANES), jnp.float32)
    jx = jax.make_jaxpr(_packed_step)(a, a)
    found = resident_purity_findings(jx, ROWS, compute_seeds=set(),
                                     lanes=LANES)
    msgs = [m for _, m in found]
    assert any("PACK" in m for m in msgs), msgs
    assert any("UNPACK" in m for m in msgs), msgs
    assert all("test_analysis.py" in locus for locus, _ in found), found


def test_r1_forward_read_of_compute_slab_is_sanctioned():
    def resident(slab):
        w = jax.lax.slice(slab, (0, 0), (ROWS // 2, LANES))
        return jnp.sum(w)

    slab = jnp.zeros((ROWS, LANES), jnp.float32)
    jx = jax.make_jaxpr(resident)(slab)
    seeds = invar_ids(jx, [(0, 1)])
    assert resident_purity_findings(jx, ROWS, seeds, lanes=LANES) == []
    # same slice, slab NOT seeded as the compute slab -> unpack
    assert resident_purity_findings(jx, ROWS, set(), lanes=LANES) != []


def test_slab_copy_counts_matches_manual_walk():
    a = jnp.zeros((ROWS // 2, LANES), jnp.float32)
    jx = jax.make_jaxpr(_packed_step)(a, a)
    counts = slab_copy_counts(jx, ROWS, lanes=LANES)
    assert counts["concatenate"] == 1
    assert counts["slice"] >= 1


# ------------------------------------------------------------------- R2 --
def test_r2_seeded_weight_upcast_fires_with_locus():
    def fwd(w, x):
        return jnp.sum(w.astype(jnp.float32) * x)

    w = jnp.zeros((256, 256), jnp.bfloat16)
    x = jnp.zeros((256, 256), jnp.float32)
    jx = jax.make_jaxpr(fwd)(w, x)
    found = dtype_policy_findings(jx, invar_ids(jx, [(0, 1)]))
    assert len(found) == 1
    locus, msg = found[0]
    assert "bfloat16 -> float32" in msg and "65536" in msg
    assert "test_analysis.py" in locus


def test_r2_non_weight_and_small_casts_are_clean():
    def fwd(w, x):
        return jnp.sum(w * x.astype(jnp.bfloat16).astype(jnp.float32))

    w = jnp.zeros((256, 256), jnp.float32)
    x = jnp.zeros((256, 256), jnp.float32)
    jx = jax.make_jaxpr(fwd)(w, x)
    # x's round trip is not weight-derived -> clean
    assert dtype_policy_findings(jx, invar_ids(jx, [(0, 1)])) == []
    # and a weight upcast below the size floor is plumbing, not traffic
    small = jax.make_jaxpr(lambda w: jnp.sum(w.astype(jnp.float32)))(
        jnp.zeros((8, 8), jnp.bfloat16))
    assert dtype_policy_findings(small, invar_ids(small, [(0, 1)])) == []


# ------------------------------------------------------------------- R3 --
def test_r3_seeded_debug_callback_fires():
    def step(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    jx = jax.make_jaxpr(step)(jnp.zeros((4,), jnp.float32))
    found = host_sync_findings(jx)
    assert any(sev == "error" and "callback" in msg
               for sev, _, msg in found), found


def test_r3_pure_math_is_clean():
    jx = jax.make_jaxpr(lambda x: jnp.tanh(x) @ x.T)(
        jnp.zeros((32, 32), jnp.float32))
    assert host_sync_findings(jx) == []


# ------------------------------------------------------------------- R4 --
def _compiled_hlo(donate):
    def step(s, b):
        return jax.tree.map(lambda l: l + b.sum(), s)

    s = {"w": jnp.zeros((256, 256), jnp.float32),
         "m": jnp.zeros((256, 256), jnp.float32)}
    b = jnp.ones((8,), jnp.float32)
    fn = jax.jit(step, donate_argnums=(0,) if donate else ())
    return fn.lower(s, b).compile().as_text()


def test_r4_seeded_missing_donation_fires():
    hlo = _compiled_hlo(donate=False)
    found = donation_findings(hlo, donated=[(0, 2)])
    assert len(found) == 1
    sev, locus, msg = found[0]
    assert sev == "error" and "input_output_alias" in locus
    assert "copied, not reused" in msg


def test_r4_honoured_donation_is_clean():
    hlo = _compiled_hlo(donate=True)
    assert sorted(aliased_params(hlo))[:2] == [0, 1]
    assert donation_findings(hlo, donated=[(0, 2)]) == []


# ------------------------------------------------------------------- R5 --
def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _pl_jaxpr(grid, block, x_shape, out_shape):
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    fn = pl.pallas_call(
        _copy_kernel, grid=grid, in_specs=[spec],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32))
    return jax.make_jaxpr(fn)(jnp.zeros(x_shape, jnp.float32))


def test_r5_seeded_vmem_blowout_fires():
    # whole-array f32 (2048,1024) in+out blocks, double-buffered: 32 MiB
    jx = _pl_jaxpr((1,), (2048, 1024), (2048, 1024), (2048, 1024))
    found = pallas_findings(jx)
    assert any(sev == "error" and "VMEM budget" in msg
               for sev, _, msg in found), found


def test_r5_seeded_nondividing_block_fires():
    jx = _pl_jaxpr((3,), (100, 512), (256, 512), (256, 512))
    found = pallas_findings(jx)
    assert any(sev == "error" and "does not tile" in msg
               for sev, _, msg in found), found


def test_r5_seeded_untiled_block_fires():
    """A (4, 512) row block over a (64, 512) array divides it but breaks the
    TPU (8, 128) rule on the second-minor dim — Mosaic refuses it."""
    jx = _pl_jaxpr((16,), (4, 512), (64, 512), (64, 512))
    found = pallas_findings(jx)
    assert any(sev == "error" and "(8, 128)" in msg
               for sev, _, msg in found), found


def test_r5_seeded_grid_undercoverage_fires():
    jx = _pl_jaxpr((1,), (256, 512), (512, 512), (512, 512))
    found = pallas_findings(jx)
    assert any(sev == "error" and "unwritten regions" in msg
               for sev, _, msg in found), found


def test_r5_wellformed_tiling_is_clean():
    jx = _pl_jaxpr((4,), (128, 512), (512, 512), (512, 512))
    assert pallas_findings(jx) == []
    [call] = pallas_calls(jx)
    assert call.grid == (4,) and call.grid_size == 4


def test_r5_covers_new_attention_variant_paths():
    """The segment/MLA/ragged kernel traces are registered hot paths, R5
    walks ALL their pallas_calls (fwd + the dQ and dK/dV backward kernels
    for the attention variants), and the production geometry lints
    clean."""
    from repro.analysis import hotpaths
    by_name = {p.name: p for p in hotpaths.kernel_paths()}
    for name, ncalls in (("kernel/flash_attention_packed", 3),
                         ("kernel/flash_attention_mla", 3),
                         ("kernel/flash_decode_ragged", 1)):
        assert name in by_name, sorted(by_name)
        p = by_name[name]
        assert len(list(pallas_calls(p.jaxpr))) == ncalls
        assert pallas_findings(p.jaxpr) == []


def test_r5_fires_on_seeded_ragged_decode_violation(monkeypatch):
    """A decode-block pick that does not tile the cache length must be a
    lint ERROR on the ragged decode trace (the real decode_block only
    returns divisors; this seeds the violation R5 is there to catch)."""
    from repro.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "decode_block", lambda L: 48)
    # fresh shapes (L=528, 528 % 48 == 0 is false: 528 = 11*48... use 520)
    q = jax.ShapeDtypeStruct((2, 1, 4, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 520, 2, 64), jnp.float32)
    lengths = jax.ShapeDtypeStruct((2,), jnp.int32)
    jx = jax.make_jaxpr(
        lambda a, b, c, l: fa.flash_decode(a, b, c, l, interpret=True))(
            q, kv, kv, lengths)
    found = pallas_findings(jx)
    assert any(sev == "error" and "does not tile" in msg
               for sev, _, msg in found), found


# ------------------------------------------------------------------- R6 --
_AG_HLO = """\
HloModule jit_decode

%body (p: (s32[], f32[64,512])) -> (s32[], f32[64,512]) {
  %x = f32[64,512] get-tuple-element(%p), index=1
  %ag = f32[64,512] all-gather(%x), replica_groups={{0,1}}, dimensions={0}
  ROOT %t = (s32[], f32[64,512]) tuple(%i, %ag)
}

%cond (p2: (s32[], f32[64,512])) -> pred[] {
  ROOT %lt = pred[] compare(%i2, %n), direction=LT
}

ENTRY %main (a: f32[64,512]) -> f32[64,512] {
  %loop = (s32[], f32[64,512]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %out = f32[64,512] get-tuple-element(%loop), index=1
}
"""


def test_r6_seeded_stray_allgather_fires():
    found = collective_findings(_AG_HLO)
    assert len(found) == 1
    sev, locus, msg = found[0]
    assert sev == "error" and locus == "hlo all-gather"
    # 64*512*4 B * (2-1)/2 per trip, x4 trips
    assert "0.25 MiB" in msg


def test_r6_allowance_covers_expected_traffic():
    assert collective_findings(_AG_HLO,
                               {"all-gather": 1 << 20}) == []


# ----------------------------------------------------- framework / sweep --
def test_rule_registry_and_selection():
    rules = get_rules(None)
    assert [r.id for r in rules] == ["R1", "R2", "R3", "R4", "R5", "R6"]
    assert [r.id for r in get_rules(["r5", "R1"])] == ["R1", "R5"]
    with pytest.raises(SystemExit):
        get_rules(["R9"])
    r3 = get_rules(["R3"])[0]
    assert r3.applies("kernel") and r3.applies("train")
    r1 = get_rules(["R1"])[0]
    assert r1.applies("train") and not r1.applies("decode")


def test_kernel_paths_sweep_clean_and_report_schema():
    findings, doc = run_analysis((), rules=["R3", "R5"],
                                 compile_paths=False, kernels=True)
    assert findings == []
    assert doc["errors"] == 0 and doc["warnings"] == 0
    assert any(p == "<kernels>:kernel/flash_attention"
               for p in doc["paths"])
    assert validate_schema(doc, ANALYSIS_SCHEMA) == []


def test_report_counts_and_schema_on_synthetic_findings():
    from repro.analysis import Finding
    f = [Finding(rule="R2", severity="error", path="train/resident/sgdm",
                 config="smollm-135m", locus="models/lm.py:1",
                 message="seeded"),
         Finding(rule="R3", severity="warn", path="serve/decode/r1/t1",
                 config="resnet18", locus="x.py:2", message="seeded")]
    doc = build_report(f, configs=["smollm-135m", "resnet18"],
                       rules=["R2", "R3"],
                       paths=["train/resident/sgdm", "serve/decode/r1/t1"],
                       skipped=[])
    assert doc["errors"] == 1 and doc["warnings"] == 1
    assert validate_schema(doc, ANALYSIS_SCHEMA) == []
    bad = dict(doc, findings=[{"rule": "R2"}])
    errs = validate_schema(bad, ANALYSIS_SCHEMA)
    assert any("missing" in e for e in errs)
    with pytest.raises(SystemExit):
        from repro.analysis import write_report
        write_report(bad, out=None)


@pytest.mark.slow
def test_full_jaxpr_sweep_is_clean_on_all_configs():
    findings, doc = run_analysis(("smollm-135m", "resnet18"),
                                 compile_paths=False)
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], [str(f) for f in errors]
    assert doc["warnings"] == 0, [str(f) for f in findings]
    # every jaxpr-capable rule actually ran on paths of its kind
    assert {"R4 (needs compiled HLO; run without --no-compile)",
            "R6 (needs compiled HLO; run without --no-compile)"} \
        == set(doc["skipped"])
    assert len(doc["paths"]) >= 20
