"""The Kerncraft-for-XLA analyzer: exact FLOP accounting through scan trip
counts, collective wire models, fusion-boundary byte accounting (the inputs
to §Roofline)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import hlo_analysis as H


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    ana = H.analyze_hlo_text(_compiled(jnp.dot, a, b).as_text())
    assert ana.mxu_flops == 2 * 64 * 128 * 32


def test_scan_trip_count_multiplies():
    """cost_analysis() counts while bodies once; our analyzer must multiply
    by the known trip count."""
    n_layers = 8

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    ws = jax.ShapeDtypeStruct((n_layers, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    compiled = _compiled(f, ws, x)
    ana = H.analyze_hlo_text(compiled.as_text())
    want = n_layers * 2 * 16 * 64 * 64
    assert ana.mxu_flops == want
    # and XLA's own analysis indeed undercounts (the reason we parse):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jax wraps it in a list
        ca = ca[0]
    assert ca["flops"] < want


def test_scan_weight_traffic_slice_sized():
    """Stacked scan weights must count one layer-slice per iteration, not
    the whole stack (else 61-layer models overcount 61x)."""
    n_layers, d = 16, 64

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, ws)[0]

    ws = jax.ShapeDtypeStruct((n_layers, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ana = H.analyze_hlo_text(_compiled(f, ws, x).as_text())
    stack_bytes = n_layers * d * d * 4
    slice_bytes = d * d * 4
    # traffic well under trips x full-stack, but at least one slice per trip
    assert ana.hbm_bytes < 0.35 * n_layers * stack_bytes
    assert ana.hbm_bytes >= n_layers * slice_bytes


def test_collective_wire_models():
    assert H._collective_wire_bytes("all-reduce", 100, 4) == \
        pytest.approx(150.0)
    assert H._collective_wire_bytes("all-gather", 100, 4) == \
        pytest.approx(75.0)
    assert H._collective_wire_bytes("reduce-scatter", 100, 4) == 300.0
    assert H._collective_wire_bytes("collective-permute", 100, 4) == 100.0
    assert H._collective_wire_bytes("all-reduce", 100, 1) == 0.0


def test_group_size_parsing():
    assert H._group_size("replica_groups=[2,4]<=[8]", 1) == 4
    assert H._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}", 1) == 4
    assert H._group_size("no groups here", 3) == 3


def test_shape_bytes():
    assert H._shape_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert H._shape_bytes("bf16[2,2]{1,0}") == 8
    assert H._shape_bytes("(s32[], f32[4]{0})") == 4 + 16
    assert H._shape_bytes("pred[10]{0}") == 10


def test_sharded_program_collectives(devices8):
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import hlo_analysis as H
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(data=2, model=4)

def f(w, x):
    y = x @ w                                   # contracting dim sharded
    return jax.lax.with_sharding_constraint(y, P("data", None))

w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
with jax.set_mesh(mesh):
    c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("model", None)),
                                 NamedSharding(mesh, P("data", "model")))
                ).lower(w, x).compile()
ana = H.analyze_hlo_text(c.as_text())
assert ana.collective_wire_bytes > 0
kinds = set(ana.collective_by_kind)
assert kinds & {"all-reduce", "reduce-scatter", "all-gather"}, kinds
print("collectives OK", dict(ana.collective_by_kind))
"""
    assert "collectives OK" in devices8(code)


def test_roofline_report_terms():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    compiled = _compiled(f, a, b)
    rep = H.roofline_from_compiled(
        compiled, arch="toy", shape="s", mesh="m", chips=1,
        model_flops_global=2 * 256**3)
    assert rep.t_compute == pytest.approx(
        rep.mxu_flops / H.PEAK_FLOPS_BF16)
    assert rep.useful_flop_ratio == pytest.approx(1.0)
    assert rep.dominant in ("compute", "memory", "collective")
    d = rep.to_dict()
    assert {"t_compute", "t_memory", "t_collective",
            "roofline_fraction"} <= set(d)


# ----------------------------------------------------------------------
# Collective wire models through real HLO text (satellite: every kind's
# byte formula, plus while-loop trip-count multiplication)
# ----------------------------------------------------------------------

def _entry(body_lines: str) -> str:
    return ("HloModule m\n\n"
            "ENTRY %main (p: f32[1024]) -> f32[1024] {\n"
            "  %p = f32[1024]{0} parameter(0)\n"
            f"{body_lines}"
            "}\n")


_KB4 = 1024 * 4          # result bytes of an f32[1024]


@pytest.mark.parametrize("kind, wire", [
    # ring wire models over a group of 4, f32[1024] result = 4096 B
    ("all-reduce", 2 * 3 / 4 * _KB4),
    ("all-gather", 3 / 4 * _KB4),
    ("reduce-scatter", 3 * _KB4),
    ("all-to-all", 3 / 4 * _KB4),
    ("collective-permute", float(_KB4)),
])
def test_collective_bytes_in_hlo_text(kind, wire):
    txt = _entry(
        f"  ROOT %c = f32[1024]{{0}} {kind}(%p), "
        "replica_groups={{0,1,2,3}}, to_apply=%sum\n")
    # disable the AR->RS recost so the raw ring formula is visible
    ana = H.analyze_hlo_text(txt, assume_rs_rewrite=False)
    assert ana.collective_wire_bytes == pytest.approx(wire)
    assert dict(ana.collective_by_kind) == {kind: pytest.approx(wire)}
    [rec] = ana.schedule
    assert rec.kind == kind and rec.group_size == 4 and rec.multiplier == 1


def test_collective_group_of_one_is_free():
    txt = _entry("  ROOT %c = f32[1024]{0} all-reduce(%p), "
                 "replica_groups={{0}}, to_apply=%sum\n")
    assert H.analyze_hlo_text(txt).collective_wire_bytes == 0.0


def test_ar_ds_recost_as_reduce_scatter():
    """An all-reduce consumed only through slices is re-costed as RS of the
    slice: (n-1)/n x slice bytes instead of 2(n-1)/n x full."""
    txt = _entry(
        "  %ar = f32[1024]{0} all-reduce(%p), "
        "replica_groups={{0,1,2,3}}, to_apply=%sum\n"
        "  ROOT %ds = f32[256]{0} dynamic-slice(%ar, %p), "
        "dynamic_slice_sizes={256}\n")
    ana = H.analyze_hlo_text(txt, assume_rs_rewrite=True)
    assert ana.collective_wire_bytes == pytest.approx(3 / 4 * 256 * 4)
    raw = H.analyze_hlo_text(txt, assume_rs_rewrite=False)
    assert raw.collective_wire_bytes == pytest.approx(2 * 3 / 4 * _KB4)


_WHILE_TXT = """HloModule m

%body (bp: (f32[256])) -> (f32[256]) {
  %bp = (f32[256]{0}) parameter(0)
  %gte = f32[256]{0} get-tuple-element(%bp), index=0
  %ar = f32[256]{0} all-reduce(%gte), replica_groups={{0,1}}, to_apply=%sum
  %sq = f32[256]{0} multiply(%ar, %ar)
  ROOT %t = (f32[256]{0}) tuple(%sq)
}

%cond (cp: (f32[256])) -> pred[] {
  %cp = (f32[256]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[256]) -> (f32[256]) {
  %a = f32[256]{0} parameter(0)
  %t0 = (f32[256]{0}) tuple(%a)
  %w = (f32[256]{0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %r = f32[256]{0} get-tuple-element(%w), index=0
}
"""


def test_while_trip_count_multiplies_collectives_and_flops():
    """Everything inside a while body — collective wire bytes, flops, HBM
    traffic — scales by the known trip count, like Kerncraft multiplying
    per-iteration cost by the loop trip count (paper §2.1)."""
    ana = H.analyze_hlo_text(_WHILE_TXT)
    per_iter_wire = 2 * (2 - 1) / 2 * 256 * 4     # AR over group of 2
    assert ana.collective_wire_bytes == pytest.approx(5 * per_iter_wire)
    assert ana.vpu_flops == 5 * 256               # the multiply, x5
    [rec] = ana.schedule
    assert rec.multiplier == 5 and rec.group_size == 2
    # HBM traffic of the body multiply: (2 operands + result) x 5 trips
    assert ana.hbm_bytes >= 5 * 3 * 256 * 4


def test_unannotated_while_counts_once():
    txt = _WHILE_TXT.replace(
        ', backend_config={"known_trip_count":{"n":"5"}}', "")
    ana = H.analyze_hlo_text(txt)
    assert ana.vpu_flops == 256
    assert ana.schedule[0].multiplier == 1


# ----------------------------------------------------------------------
# The registered "hlo-roofline" model (acceptance: resolves via
# MODEL_REGISTRY and round-trips through reports.to_json/from_json)
# ----------------------------------------------------------------------

def test_hlo_roofline_model_registered():
    from repro.core import MODEL_REGISTRY, resolve_model
    m = resolve_model("hlo-roofline")
    assert m is MODEL_REGISTRY["hlo-roofline"]
    assert m.input_kind == "hlo"


def test_hlo_roofline_result_json_round_trip():
    from repro.core import analyze, load_machine, reports

    res = analyze(_WHILE_TXT, load_machine("V5E"), model="hlo-roofline",
                  name="while-toy")
    d = res.to_dict()
    assert d["model"] == "hlo-roofline"
    rebuilt = reports.from_json(reports.to_json(res))
    assert isinstance(rebuilt, H.HLORooflineResult)
    assert rebuilt.to_dict() == d
    # machine constants flow from the v5e yaml, not the module fallbacks
    assert res.peak_flops == pytest.approx(1.97e14)
    assert res.hbm_bandwidth == pytest.approx(819e9)
    # the text report renders from the same dict
    assert "HLO Roofline" in reports.json_report(res)


def test_hlo_roofline_uses_machine_dtype():
    from repro.core import analyze, load_machine

    res32 = analyze(_WHILE_TXT, load_machine("V5E"), model="hlo-roofline",
                    name="while-toy", dtype="FP32")
    assert res32.peak_flops == pytest.approx(8.25e12)


def test_hlo_roofline_rejects_non_tpu_machine_and_unknown_dtype():
    """No silent v5e-constant substitution: an x86 cache machine or a dtype
    the machine lacks must raise, not answer with wrong numbers."""
    from repro.core import analyze, load_machine

    with pytest.raises(ValueError, match="no TPU fields"):
        analyze(_WHILE_TXT, load_machine("IVY"), model="hlo-roofline")
    with pytest.raises(ValueError, match=r"no peak flops for dtype "
                                         r"'INT8'.*BF16.*FP32"):
        analyze(_WHILE_TXT, load_machine("V5E"), model="hlo-roofline",
                dtype="INT8")


def test_vpu_only_program_gets_compute_term():
    """A program with no matmuls (pure elementwise/stencil work) must still
    report a nonzero compute bound from the VPU peak."""
    from repro.core import analyze, load_machine

    res = analyze(_WHILE_TXT, load_machine("V5E"), model="hlo-roofline",
                  name="while-toy")
    assert res.mxu_flops == 0 and res.vpu_flops > 0
    assert res.t_compute == pytest.approx(res.vpu_flops
                                          / res.vpu_peak_flops)
    assert res.arithmetic_intensity > 0


# ----------------------------------------------------------------------
# Property: per-op roll-up conservation on randomized fusion/while nests
# (the fleet analyzer's invariant; ISSUE 8 satellite)
# ----------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # container without hypothesis
    from _hypothesis_fallback import given, settings, st

_T16 = "f32[16,16]{1,0}"
_EW_OPS = ("add", "multiply", "maximum", "subtract")
_COLL_KINDS = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute")


def _nest_module(trips, ew, kind, with_coll, with_fusion, inner) -> str:
    """A synthetic module: a chain of trip-annotated whiles whose bodies
    hold a dot, an elementwise chain, optionally a collective, a fusion
    (exp+multiply inside), and — in the first body — an inner while."""
    comps, entry = [], ["  %p = f32[16,16]{1,0} parameter(0)\n"]
    prev = "p"
    for i, t in enumerate(trips):
        body = [
            f"  %bp{i} = ({_T16}) parameter(0)\n",
            f"  %gte{i} = {_T16} get-tuple-element(%bp{i}), index=0\n",
            f"  %dot{i} = {_T16} dot(%gte{i}, %gte{i}), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"]
        cur = f"dot{i}"
        for j in range(ew):
            op = _EW_OPS[j % len(_EW_OPS)]
            body.append(f"  %e{i}_{j} = {_T16} {op}(%{cur}, %gte{i})\n")
            cur = f"e{i}_{j}"
        if with_coll:
            body.append(f"  %c{i} = {_T16} {kind}(%{cur}), "
                        "replica_groups={{0,1,2,3}}, to_apply=%sum\n")
            cur = f"c{i}"
        if with_fusion:
            comps.append(
                f"%fused_{i} (fp{i}: f32[16,16]) -> f32[16,16] {{\n"
                f"  %fp{i} = {_T16} parameter(0)\n"
                f"  %fe{i} = {_T16} exponential(%fp{i})\n"
                f"  ROOT %fm{i} = {_T16} multiply(%fe{i}, %fe{i})\n"
                "}\n")
            body.append(f"  %fu{i} = {_T16} fusion(%{cur}), kind=kLoop, "
                        f"calls=%fused_{i}\n")
            cur = f"fu{i}"
        if inner and i == 0:
            comps.append(
                f"%ibody_{i} (ibp{i}: (f32[16,16])) -> (f32[16,16]) {{\n"
                f"  %ibp{i} = ({_T16}) parameter(0)\n"
                f"  %igte{i} = {_T16} get-tuple-element(%ibp{i}), index=0\n"
                f"  %im{i} = {_T16} multiply(%igte{i}, %igte{i})\n"
                f"  ROOT %ibt{i} = ({_T16}) tuple(%im{i})\n"
                "}\n")
            comps.append(
                f"%icond_{i} (icp{i}: (f32[16,16])) -> pred[] {{\n"
                f"  %icp{i} = ({_T16}) parameter(0)\n"
                f"  ROOT %ilt{i} = pred[] constant(false)\n"
                "}\n")
            body += [
                f"  %it{i} = ({_T16}) tuple(%{cur})\n",
                f"  %iw{i} = ({_T16}) while(%it{i}), "
                f"condition=%icond_{i}, body=%ibody_{i}, "
                f'backend_config={{"known_trip_count":{{"n":"{inner}"}}}}\n',
                f"  %ig{i} = {_T16} get-tuple-element(%iw{i}), index=0\n"]
            cur = f"ig{i}"
        body.append(f"  ROOT %bt{i} = ({_T16}) tuple(%{cur})\n")
        comps.append(f"%body_{i} (bp{i}: (f32[16,16])) -> (f32[16,16]) {{\n"
                     + "".join(body) + "}\n")
        comps.append(f"%cond_{i} (cp{i}: (f32[16,16])) -> pred[] {{\n"
                     f"  %cp{i} = ({_T16}) parameter(0)\n"
                     f"  ROOT %lt{i} = pred[] constant(false)\n"
                     "}\n")
        entry += [
            f"  %t{i} = ({_T16}) tuple(%{prev})\n",
            f"  %w{i} = ({_T16}) while(%t{i}), condition=%cond_{i}, "
            f"body=%body_{i}, "
            f'backend_config={{"known_trip_count":{{"n":"{t}"}}}}\n',
            f"  %g{i} = {_T16} get-tuple-element(%w{i}), index=0\n"]
        prev = f"g{i}"
    entry.append(f"  ROOT %out = {_T16} add(%{prev}, %{prev})\n")
    return ("HloModule m\n\n" + "\n".join(comps)
            + "\nENTRY %main (p: f32[16,16]) -> f32[16,16] {\n"
            + "".join(entry) + "}\n")


@settings(max_examples=25, deadline=None)
@given(trips=st.lists(st.integers(1, 5), min_size=1, max_size=3),
       ew=st.integers(0, 3),
       kind=st.sampled_from(_COLL_KINDS),
       with_coll=st.booleans(),
       with_fusion=st.booleans(),
       inner=st.integers(0, 4))
def test_per_op_rollup_conserves_on_random_nests(trips, ew, kind,
                                                 with_coll, with_fusion,
                                                 inner):
    """analyze_hlo_text(per_op=True): summing any OpCost field over the
    records reproduces the module total, on arbitrary while/fusion nests;
    per_op recording never perturbs the totals themselves."""
    txt = _nest_module(trips, ew, kind, with_coll, with_fusion, inner)
    ana = H.analyze_hlo_text(txt, per_op=True)
    base = H.analyze_hlo_text(txt)
    # recording is observation-only: totals match the plain walk exactly
    assert (ana.mxu_flops, ana.vpu_flops, ana.hbm_bytes,
            ana.collective_wire_bytes) == \
        (base.mxu_flops, base.vpu_flops, base.hbm_bytes,
         base.collective_wire_bytes)
    # conservation: per-op sums == module totals (same accumulations)
    assert sum(o.mxu_flops for o in ana.ops) == \
        pytest.approx(ana.mxu_flops, rel=1e-12)
    assert sum(o.vpu_flops for o in ana.ops) == \
        pytest.approx(ana.vpu_flops, rel=1e-12)
    assert sum(o.hbm_bytes for o in ana.ops) == \
        pytest.approx(ana.hbm_bytes, rel=1e-12)
    assert sum(o.wire_bytes for o in ana.ops) == \
        pytest.approx(ana.collective_wire_bytes, rel=1e-12)
    # trip counts: every record in body_i carries multiplier trips[i],
    # and the inner while nests multiplicatively under trips[0]
    for i, t in enumerate(trips):
        recs = [o for o in ana.ops if o.computation == f"body_{i}"]
        assert recs and all(o.multiplier == t for o in recs)
    if inner:
        recs = [o for o in ana.ops if o.computation == "ibody_0"]
        assert recs and all(o.multiplier == trips[0] * inner for o in recs)
    # fusion boundary: internal flops fold into the owning fusion record
    if with_fusion:
        fus = [o for o in ana.ops if o.opcode == "fusion"]
        assert len(fus) == len(trips)
        for i, o in enumerate(sorted(fus, key=lambda o: o.computation)):
            assert o.vpu_flops == 2 * 256 * o.multiplier   # exp + multiply
