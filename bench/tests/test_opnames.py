"""bench.opnames: the op names decoded from a profile's raw protobuf, the
reading of scopes from an op name, and device time by scope, on hand-made
ops and on the chip profiles committed under ``data/``."""
import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import opnames, xplane
from bench.xplane import DeviceOp, Trace

DATA = Path(__file__).resolve().parent / "data"
SBM = "fedgat-sbm100k-k8"


def _unzip(tmp_path, name):
    dst = tmp_path / "t.xplane.pb"
    with gzip.open(DATA / name, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(dst)


@pytest.fixture(scope="module")
def sbm(tmp_path_factory):
    """The committed sbm_100k chip profile (recorded before the scopes):
    its trace, the decoded op names, and the ops paired with them."""
    rec = json.loads((DATA / f"{SBM}.json").read_text())
    path = _unzip(tmp_path_factory.mktemp("sbm"), rec["trace"])
    trace = xplane.load(path)
    table = opnames.read(path)
    return trace, table, opnames.named_ops(trace, table)


def test_every_device_op_joins_its_event(sbm):
    trace, table, _ = sbm
    for chip, ops in trace.ops.items():
        keys = table[chip]
        inside = [op for op in ops if op.start_ns > trace.window[0]]
        assert inside and all((op.start_ns, op.name) in keys for op in inside)


def test_op_names_cover_the_device_time(sbm):
    _, _, named = sbm
    total = named_time = 0
    for pairs in named.values():
        for op, name in pairs:
            if xplane.is_container(op.name):
                continue
            total += op.end_ns - op.start_ns
            named_time += (op.end_ns - op.start_ns) if name else 0
    assert named_time >= 0.99 * total


def test_scatter_adds_resolve_to_the_steps_backward(sbm):
    _, _, named = sbm
    scatters = {name for pairs in named.values() for op, name in pairs
                if name.endswith("scatter-add")}
    assert scatters == {"jit(step)/vmap()/while/body/closed_call/transpose(jvp())/scatter-add"}


def test_trim_keeps_what_is_read(sbm, tmp_path):
    trace, table, _ = sbm
    raw = (DATA / json.loads((DATA / f"{SBM}.json").read_text())["trace"]).read_bytes()
    out = str(tmp_path / "trimmed.xplane.pb.gz")
    opnames.trim(gzip.decompress(raw), trace.window, out)
    path = _unzip(tmp_path, out)
    again = xplane.load(path)
    assert again.window == trace.window and again.marker_ns == trace.marker_ns
    assert again.ops == trace.ops
    kept = opnames.read(path)
    for chip, ops in trace.ops.items():
        for op in ops:
            if op.start_ns > trace.window[0]:
                assert kept[chip][(op.start_ns, op.name)] == table[chip][(op.start_ns, op.name)]


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/vmap()/while/body/closed_call/transpose(jvp(layer2))/nbr_gather/scatter-add",
     [("step", False), ("while", False), ("body", False), ("closed_call", False),
      ("layer2", True), ("nbr_gather", True), ("scatter-add", True)]),
    ("jit(step)/vmap()/while/body/closed_call/jvp(layer1)/nbr_gather/gather",
     [("step", False), ("while", False), ("body", False), ("closed_call", False),
      ("layer1", False), ("nbr_gather", False), ("gather", False)]),
    ("jit(step)/transpose(jvp(transpose(jvp())))/mul", [("step", False), ("mul", True)]),
    ("jit(evaluate)/layer1/jit(_cheb_attn)/pallas_call",
     [("evaluate", False), ("layer1", False), ("_cheb_attn", False), ("pallas_call", False)]),
    ("jit(step)/transpose(jvp(hnb,hnbo->hno))/dot_general",
     [("step", False), ("hnb,hnbo->hno", True), ("dot_general", True)]),
    ("data['h']", [("data['h']", False)]),
    ("", []),
])
def test_scopes_of_an_op_name(op_name, want):
    assert opnames.scopes(op_name) == want


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/vmap()/fold/add", "step"), ("jit(evaluate)/layer2/exp", "evaluate"),
    ("jit(<unknown>)/add", "<unknown>"), ("data['h']", ""), ("", ""),
])
def test_program_of_an_op_name(op_name, want):
    assert opnames.program(op_name) == want


# Two chips, a window of [0, 1000) ns and 2 rounds. Every op name below is
# one the scoped program writes; ``while`` is a container and never counts.
HAND_OPS = [
    (0, 100, "%fusion.1", "jit(step)/vmap()/while/body/closed_call/jvp(layer1)/nbr_gather/gather"),
    (100, 300, "%fusion.2", "jit(step)/vmap()/while/body/closed_call/transpose(jvp(layer1))/nbr_gather/scatter-add"),
    (300, 340, "%_cheb_attn.6", "jit(step)/vmap()/while/body/closed_call/jvp(layer1)/jit(_cheb_attn)/pallas_call"),
    (340, 600, "%fusion.3", "jit(step)/vmap()/while/body/closed_call/transpose(jvp(layer2))/nbr_gather/scatter-add"),
    (600, 650, "%fusion.4", "jit(step)/vmap()/while/body/closed_call/jvp(layer2)/dot_general"),
    (650, 660, "%fusion.5", "jit(step)/vmap()/while/body/closed_call/transpose(jvp(loss))/mul"),
    (660, 700, "%fusion.6", "jit(step)/vmap()/while/body/closed_call/adam/sqrt"),
    (700, 720, "%fusion.7", "jit(step)/vmap()/fold/add"),
    (720, 800, "%fusion.8", "jit(evaluate)/layer1/nbr_gather/gather"),
    (800, 830, "%fusion.9", "jit(evaluate)/layer2/exp"),
    (830, 840, "%copy.1", ""),
    (0, 720, "%while.1", "jit(step)/vmap()/while"),
]


def _hand_named():
    ops = {chip: [DeviceOp(s, e, name) for s, e, name, _ in HAND_OPS] for chip in (0, 1)}
    # Chip 1 ran its layer-2 scatter-add 100 ns longer.
    ops[1][3] = DeviceOp(340, 700, "%fusion.3")
    trace = Trace(window=(0, 1000), marker_ns=0, ops=ops)
    table = {chip: {(op.start_ns, op.name): n for op, (_, _, _, n) in zip(ops[chip], HAND_OPS)}
             for chip in ops}
    return opnames.named_ops(trace, table)


@pytest.mark.parametrize("metric, ns_per_chip", [
    # layer 1: gather 100 + scatter-add 200 + kernel 40, on each chip.
    ("layer1_device_ms", (340 + 340) / 2),
    # layer 2: scatter-add 260 / 360 + dot 50.
    ("layer2_device_ms", (310 + 410) / 2),
    # the scatter-adds of both layers: 200 + 260 / 200 + 360.
    ("nbr_gather_bwd_ms", (460 + 560) / 2),
    # evaluation: 80 + 30.
    ("evaluate_device_ms", 110),
])
def test_device_time_by_scope_on_hand_made_ops(metric, ns_per_chip):
    assert opnames.metrics(_hand_named(), rounds=2)[metric] == pytest.approx(ns_per_chip / 1e6 / 2)


def test_breakdown_accounts_for_every_op():
    parts = opnames.breakdown(_hand_named(), rounds=1)["ms_per_round"]
    ns = {"step:layer1": 340, "step:layer2": 360, "step:loss": 10, "step:adam": 40,
          "step:fold": 20, "evaluate": 110, "no op name": 10}
    assert parts == pytest.approx({k: v / 1e6 for k, v in ns.items()})


def test_a_trace_without_scopes_reads_nothing(sbm):
    _, _, named = sbm
    assert opnames.metrics(named, rounds=3) == {
        "layer1_device_ms": None, "layer2_device_ms": None,
        "nbr_gather_bwd_ms": None, "evaluate_device_ms": None}


SCOPED = [p for p in sorted(DATA.glob("*.json")) if "scoped" in json.loads(p.read_text())]
METRICS = ("layer1_device_ms", "layer2_device_ms", "nbr_gather_bwd_ms", "evaluate_device_ms")


@pytest.fixture(scope="module")
def scoped_runs(tmp_path_factory):
    """Each committed profile of the scoped program, read as the chip run
    read it: {record path: (record, ops paired with their op names)}."""
    out = {}
    for path in SCOPED:
        rec = json.loads(path.read_text())
        raw = _unzip(tmp_path_factory.mktemp(path.stem), rec["trace"])
        out[path] = (rec, opnames.named_ops(xplane.load(raw), opnames.read(raw)))
    return out


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", SCOPED, ids=[p.stem for p in SCOPED])
def test_scoped_chip_run_reads_again(scoped_runs, path, metric):
    rec, named = scoped_runs[path]
    assert opnames.metrics(named, rec["rounds"])[metric] == pytest.approx(
        rec["scoped"][metric], rel=1e-12)


@pytest.mark.parametrize("path", SCOPED, ids=[p.stem for p in SCOPED])
def test_scoped_chip_run_leaves_little_unscoped(scoped_runs, path):
    """Ops under none of the program's scopes or named programs, and ops
    with no op name, take under 5 % of the device time."""
    rec, named = scoped_runs[path]
    parts = opnames.breakdown(named, rec["rounds"])["ms_per_round"]
    rest = sum(v for k, v in parts.items() if k in ("step:unscoped", "other", "no op name"))
    assert rest < 0.05 * sum(parts.values())
