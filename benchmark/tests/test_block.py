"""CPU tests of what ISSUE 34 added to the benchmark: reduce/block.py (an
op's scope by the innermost vocabulary segment of its path, phases, `rest`,
the share named) and its nine readers, on a fixture cut from a TPU v5e trace
of bert-base.pretrain-seq128, and on the older fixtures, whose programs lack
the names. No number here is a device number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest                  # noqa: E402
from benchmark.reduce import block                       # noqa: E402

SIX = ["bert-base.pretrain-seq512", "bert-base.pretrain-seq512-dp4",
       "bert-base.pretrain-seq128", "olmoe-1b-7b.pretrain-seq4096",
       "ouro-2.6b.pretrain-seq4096-b1",
       "granite-4.0-h-micro.pretrain-seq8192-b1"]
FIVE = [c for c in SIX if not c.startswith("olmoe")]
# manifest entry -> (its cells, scopes, phases; None: the share named)
BLOCK_METRICS = {
    "block_attn_proj_ms_per_step.tokens":
        (SIX, (block.QKV, block.WO), block.STEP_PHASES),
    "block_attn_proj_recompute_ms_per_step.tokens":
        (SIX, (block.QKV, block.WO), ("recompute",)),
    "block_attn_core_ms_per_step.tokens":
        (SIX, (block.ATTN,), block.STEP_PHASES),
    "block_mlp_ms_per_step.tokens":
        (FIVE, (block.MLP_UP, block.MLP_DOWN), block.STEP_PHASES),
    "block_mlp_recompute_ms_per_step.tokens":
        (FIVE, (block.MLP_UP, block.MLP_DOWN), ("recompute",)),
    "block_norm_ms_per_step.tokens":
        (SIX, (block.NORM,), block.STEP_PHASES),
    "head_ms_per_step.tokens": (SIX, (block.HEAD,), block.STEP_PHASES),
    "step_named_pct.tokens": (SIX, None, None),
    "ssm_scan_inchunk_ms_per_step.tokens":
        (["granite-4.0-h-micro.pretrain-seq8192-b1"], (block.SSD_INCHUNK,),
         block.STEP_PHASES),
}
FWD = "jit(step)/jvp(hetu_fwd)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(hetu_fwd))/while/body/closed_call/checkpoint/"


def _block_fixture(name="block_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("op_name, scope", [
    (FWD + "hetu_blk_mlp_up/btd,df->btf/dot_general", block.MLP_UP),
    (BWD + "hetu_blk_qkv/dot_general", block.QKV),
    (BWD + "rematted_computation/hetu_blk_qkv/tanh", block.QKV),
    # the innermost name of the vocabulary wins
    ("jit(step)/jvp(hetu_fwd)/hetu_exit/hetu_head/fused_ce_fwd/pallas_call:",
     block.HEAD),
    ("jit(step)/jvp(hetu_fwd)/hetu_exit/mul", "hetu_exit"),
    (FWD + "hetu_ssm_scan/hetu_ssd_inchunk/bcgrls,bcsgrp->bclgrp/dot_general",
     block.SSD_INCHUNK),
    (FWD + "hetu_ssm_scan/softplus", "hetu_ssm_scan"),
    (FWD + "hetu_blk_attn/flash_fwd/pallas_call:", block.ATTN),
    # a wrapped segment is peeled, a kernel's too
    (BWD + "hetu_blk_attn/transpose(jvp(flash_bwd))/pallas_call", block.ATTN),
    ("jit(step)/transpose(jvp(hetu_head))/mul", block.HEAD),
    ("jit(step)/hetu_opt/Optimizer_SGDOptimizer_37/fused_sgd", "hetu_opt"),
    # by segment, never by substring: a checkpoint name is not a scope, and
    # a longer segment that holds a scope's name is not that scope
    (FWD + "hetu_attn_o/add", None),
    (FWD + "hetu_blk_attn_more/add", None),
    (FWD + "xhetu_head/add", None),
    # under hetu_fwd alone, and no path at all
    (FWD + "add", None),
    ("", None),
])
def test_block_scope_is_the_innermost_vocabulary_segment(op_name, scope):
    assert block.scope_of(op_name) == scope


def test_block_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines."""
    fx = _block_fixture()
    ops = fx["chips"][0]["ops"]
    table = block.reduce_block(fx, steps=1)
    # the fixture's ops do not nest: self time is duration
    whole = sum(op[2] for op in ops)
    assert table["device_self_ms_per_step"] == pytest.approx(whole / 1e6)

    def phase(op_name):
        if "hetu_opt" in op_name:
            return "opt"
        if "transpose(" not in op_name:
            return "fwd"
        return "recompute" if "rematted_computation" in op_name else "bwd"

    with_path = [op for op in ops if op[3]]
    for scope in block.SCOPES:
        for p in block.PHASES:
            want = sum(op[2] for op in with_path
                       if f"/{scope}/" in op[3] and phase(op[3]) == p) / 1e6
            got = table["scope_ms_per_step"][scope][p]
            if scope in (block.HEAD, block.EMBED) and p == "bwd":
                continue        # + a path-less op each, below
            assert got == pytest.approx(want), (scope, p)
    # every block scope of a BERT layer is there in the phases it runs in:
    # the head and the embedding sit outside the layers' checkpoint, flash's
    # o and lse are kept, and `wo` and `w2` are not in this cut's recompute
    by = table["scope_ms_per_step"]
    for scope in block.BLOCK + (block.EMBED, block.HEAD):
        assert by[scope]["fwd"] > 0 and by[scope]["bwd"] > 0, scope
    for scope in (block.QKV, block.MLP_UP, block.NORM):
        assert by[scope]["recompute"] > 0, scope
    assert by[block.HEAD]["recompute"] == by[block.EMBED]["recompute"] == 0
    assert sum(by[block.SSD_INCHUNK].values()) == 0
    assert by["hetu_opt"]["opt"] > 0 and not sum(
        by["hetu_opt"][p] for p in block.STEP_PHASES)
    # rest: a path under hetu_fwd alone (the scan's stacks and slices)
    rest = [op for op in with_path if block.scope_of(op[3]) is None]
    assert len(rest) == 4
    assert sum(by[block.REST].values()) == pytest.approx(
        sum(op[2] for op in rest) / 1e6)
    # a path-less op (the compiler's copy) takes the scope AND the phase of
    # the op that reads its result
    orphans = [op for op in ops if not op[3]]
    assert [op[0].split(" = ")[0] for op in orphans] == [
        "%copy-done.106", "%copy-done.10"]
    for orphan, scope in zip(orphans, (block.HEAD, block.EMBED)):
        name = orphan[0].split(" = ")[0]
        reader = [op for op in with_path
                  if name + ")" in op[0] or name + "," in op[0]]
        assert reader and all(f"/{scope}/" in op[3] for op in reader)
        want = sum(op[2] for op in with_path if f"/{scope}/" in op[3]
                   and phase(op[3]) == "bwd") + orphan[2]
        assert by[scope]["bwd"] == pytest.approx(want / 1e6), scope
    # rows and rest sum to the whole, phase by phase
    for p in block.PHASES:
        assert sum(by[s][p] for s in by) == pytest.approx(
            sum(op[2] for op in with_path if phase(op[3]) == p) / 1e6
            + (sum(op[2] for op in orphans) / 1e6 if p == "bwd" else 0))
    assert table["named_pct"] == pytest.approx(
        100 * (1 - sum(op[2] for op in rest) / whole))
    rows = table["instructions"]
    assert rows == sorted(rows, key=lambda r: -r["ms_per_step"])
    assert {"mosaic:flash_fwd", "mosaic:flash_bwd"} <= {
        r["family"] for r in rows if r["scope"] == block.ATTN}
    text = block.render(table)
    assert "hetu_blk_mlp_down" in text and "rest" in text
    assert f"{table['named_pct']:.1f} %" in text


def test_block_collectives_count_as_named_and_steps_divide():
    """A collective under no scope is `rest` in the table and still named;
    two traced steps on two chips halve and halve again."""
    fx = _block_fixture()
    ops = fx["chips"][0]["ops"]
    end = ops[-1][1] + ops[-1][2]
    reduce = ["%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), "
              "replica_groups={}", end, 1e6,
              "jit(step)/transpose(jvp(hetu_fwd))/add_any"]
    one = block.reduce_block(fx, steps=1)
    fx["chips"][0]["ops"] = ops + [reduce]
    fx["chips"].append({"chip": 1, "modules": [],
                        "ops": fx["chips"][0]["ops"]})
    two = block.reduce_block(fx, steps=2)
    assert two["scope_ms_per_step"][block.REST]["collective"] == \
        pytest.approx(0.5)
    assert two["device_self_ms_per_step"] == pytest.approx(
        (one["device_self_ms_per_step"] + 1.0) / 2)
    unnamed = sum(ms for p, ms in two["scope_ms_per_step"][block.REST].items()
                  if p != "collective")
    assert two["named_pct"] == pytest.approx(
        100 * (1 - unnamed / two["device_self_ms_per_step"]))
    assert two["scope_ms_per_step"][block.QKV]["fwd"] == pytest.approx(
        one["scope_ms_per_step"][block.QKV]["fwd"] / 2)


@pytest.mark.parametrize("metric", sorted(BLOCK_METRICS))
def test_block_manifest_entry_and_reader_on_the_fixture(metric, monkeypatch):
    """Each of the nine entries: its cells in the manifest, and its reader
    through `for_run`, as the harness calls it."""
    cells, scopes, phases = BLOCK_METRICS[metric]
    entry = [m for m in manifest.load(ROOT)["per_layer"]
             if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["workloads"] == cells
    assert entry[0]["source"] == "device_trace"
    assert entry[0]["moves"] == "tokens_per_s"
    assert entry[0]["layer"] == "flagship step"
    cell = manifest.resolve(ROOT, cells[0])
    assert metric in [m["name"] for m in cell.per_layer]
    table = block.reduce_block(_block_fixture(), steps=1)
    monkeypatch.setattr(block, "for_run", lambda run: table)
    value = manifest.reader(cell, metric).read(
        {"trace": {"steps": 1}, "cell": cell})
    if scopes is None:
        assert value == table["named_pct"] and 90 < value < 100
        return
    by = table["scope_ms_per_step"]
    assert value == pytest.approx(
        sum(by[s][p] for s in scopes for p in phases))
    # a scope the program did not write reads 0.0 beside those it did
    assert (value == 0.0) == (scopes == (block.SSD_INCHUNK,))


def test_block_metrics_are_not_for_the_executor_cell():
    cell = manifest.resolve(ROOT, "wdl-criteo.local-table-bs128")
    assert not {m["name"] for m in cell.per_layer} & set(BLOCK_METRICS)
    olmoe = manifest.resolve(ROOT, "olmoe-1b-7b.pretrain-seq4096")
    assert {m["name"] for m in olmoe.per_layer} & set(BLOCK_METRICS) == {
        m for m, (cells, _s, _p) in BLOCK_METRICS.items() if cells == SIX}


@pytest.mark.parametrize("fixture", [
    "inside_two_chips.json", "inside_executor.json", "moe_one_chip.json",
    "loop_one_chip.json", "ssm_one_chip.json"])
def test_block_readers_return_nothing_without_the_names(fixture, monkeypatch):
    """A program that wrote none of the new names (the parent of PR 34, with
    or without the older scopes; the graph executor's step): no table, and
    every reader leaves its metric out without raising."""
    raw = _block_fixture(fixture)
    assert block.reduce_block(raw, steps=1) is None
    assert "no hetu_blk_" in block.render(None)
    monkeypatch.setattr(block, "_reduced",
                        lambda path, steps: block.reduce_block(raw, steps))
    monkeypatch.setattr(block, "newest_xplane", lambda d: d)
    for metric, (cells, _scopes, _phases) in BLOCK_METRICS.items():
        cell = manifest.resolve(ROOT, cells[0])
        run = {"trace": {"steps": 1}, "cell": cell}
        assert manifest.reader(cell, metric).read(run) is None, metric


def test_block_readers_return_nothing_on_an_untraced_run_or_a_bad_trace(
        tmp_path, capsys):
    import dataclasses
    cell = manifest.resolve(ROOT, "bert-base.pretrain-seq128")
    # a traced run whose trace is not on disk: the reason goes to stderr
    elsewhere = dataclasses.replace(cell, bench_dir=str(tmp_path))
    for metric in BLOCK_METRICS:
        if cell.name not in BLOCK_METRICS[metric][0]:
            continue
        reader = manifest.reader(cell, metric)
        assert reader.read({"trace": None, "cell": cell}) is None
        assert reader.read({"trace": {"steps": 5}, "cell": elsewhere}) is None
    assert "Traceback" in capsys.readouterr().err
