"""Benchmark harness — prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Headline (BASELINE.md north star): ResNet-18 / CIFAR10-shape training through
the define-then-run Executor on the real chip, samples/sec/chip, best over
{f32, bf16} x {bs 128, 256} plus bf16 x bs 512 (f32 falls behind well
before bs 512, so that cell is skipped). Round-3 changes: bf16 conv backward
fixed, device-resident dataset slicing (zero per-step H2D), rng folded into
the jit, one host read of the loss closing each timed window.
``detail`` carries each config's samples/s + step ms + MFU (XLA cost-analysis
flops over the device's tabled peak), the flagship transformer tokens/s, and
a WDL-Criteo run through a real local PS cluster (scheduler + 2 servers,
Hybrid mode) with the prefetch on/off A/B.

Syncs once per timed window: a host<->device roundtrip per step would be
counted into the step time.

The run needs a TPU: the probe fails, and the run ends non-zero before any
section, on any other backend — unless HETU_BENCH_SMOKE=1, which shrinks
every section to a CPU-sized smoke for the tests.

vs_baseline: the reference publishes no numbers (BASELINE.md); recorded
baseline = our round-1 f32 measurement (4929.1 samples/s on v5e-1).
"""
import contextlib
import glob
import json
import os
import re
import signal
import sys
import tempfile
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC = 4929.1

def _mfu(flops_per_step, step_s):
    """MFU against the peak profiler.DEVICE_PEAKS holds for the device this
    section child runs on, rounded; None for a device_kind the table does
    not know."""
    import jax
    mfu = _profiler().mfu(flops_per_step, step_s,
                          jax.devices()[0].device_kind)
    return None if mfu is None else round(mfu, 4)


_PROFILER = None


def _profiler():
    """``hetu_tpu/telemetry/profiler.py`` loaded by FILE PATH (shared with
    bin/hetuprof): the driver parent must stay jax-free and importing the
    ``hetu_tpu`` package pulls jax. The module is stdlib-only by
    contract.

    One process per chip: this parent never initialises a jax backend, so
    each section child it starts — one at a time — is the only process on
    the chip."""
    global _PROFILER
    if _PROFILER is None:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "hetu_tpu", "telemetry", "profiler.py")
        spec = importlib.util.spec_from_file_location("_hetuprof", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules.setdefault("_hetuprof", mod)   # dataclasses need this
        spec.loader.exec_module(sys.modules["_hetuprof"])
        _PROFILER = sys.modules["_hetuprof"]
    return _PROFILER


def _attn_flops(batch, seq, n_layers, d_model, causal):
    """Attention-score matmul FLOPs (the 6ND rule excludes them) — the
    formula lives in hetu_tpu.telemetry.profiler.attn_flops now so hetutop
    reports the same two denominators (docs/ROOFLINE.md)."""
    return _profiler().attn_flops(batch, seq, n_layers, d_model, causal)


def _import_models(suite):
    """Import examples/<suite>/models fresh — the cnn and ctr suites both
    name their package ``models``, so the cached module must be dropped."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", suite)
    if path in sys.path:
        sys.path.remove(path)
    sys.path.insert(0, path)
    for mod in [m for m in sys.modules
                if m == "models" or m.startswith("models.")]:
        del sys.modules[mod]
    import models
    return models


def bench_resnet18(batch_size=128, warmup=5, iters=30, dtype=None):
    # stdout must stay clean: the driver's contract is ONE JSON line, and
    # the example model zoo prints "Building ..." banners
    with contextlib.redirect_stdout(sys.stderr):
        return _bench_resnet18(batch_size, warmup, iters, dtype)


def _bench_resnet18(batch_size, warmup, iters, dtype):
    import hetu_tpu as ht
    models = _import_models("cnn")

    rng = np.random.RandomState(0)
    n = batch_size * 4
    data_x = rng.randn(n, 3, 32, 32).astype(np.float32)
    data_y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    x = ht.dataloader_op([ht.Dataloader(data_x, batch_size, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(data_y, batch_size, "train")])
    loss, y = models.resnet18(x, y_, 10)
    opt = ht.optim.MomentumOptimizer(learning_rate=0.1)
    train_op = opt.minimize(loss)
    kwargs = {} if dtype is None else {"dtype": dtype}
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.tpu(0), seed=0,
                     **kwargs)

    for _ in range(warmup):
        ex.run("train")
    float(np.mean(ex.run("train")[0].asnumpy()))  # drain the queue

    t0 = time.time()
    for _ in range(iters - 1):
        ex.run("train")
    last = ex.run("train")[0]
    float(np.mean(last.asnumpy()))  # one sync for the whole window
    dt = (time.time() - t0) / iters

    cost = ex.subexecutors["train"].last_cost_analysis() or {}
    flops = cost.get("flops")
    return batch_size / dt, dt * 1000, _mfu(flops, dt)



def bench_introspect_overhead(width=512, batch=512, warmup=None, iters=60,
                              cadence=None):
    """Measured hetuscope introspection overhead (docs/OBSERVABILITY.md
    acceptance: <5% of step time at the default cadence) — two identical
    MLP trainers, introspect off vs on, same shapes/seed, timed back to
    back on CPU (a framework-overhead measurement the SECTION_ENV pin
    keeps deterministic). The on-window pays
    the real amortized cost: 1-in-cadence steps run the stats variant and
    its one extra device fetch."""
    import hetu_tpu as ht
    from hetu_tpu.telemetry import scope as scope_mod

    cadence = cadence or scope_mod.DEFAULT_CADENCE
    if warmup is None:
        warmup = cadence + 5   # must compile BOTH variants of the on-step

    def build(introspect):
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        h = x
        for i in range(3):
            w = ht.init.random_normal((width, width), stddev=0.05,
                                      name=f"w{i}")
            h = ht.relu_op(ht.matmul_op(h, w))
        wo = ht.init.random_normal((width, 8), stddev=0.05, name="wo")
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, wo), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         seed=0, introspect=introspect)
        rng = np.random.RandomState(0)
        bx = rng.randn(batch, width).astype(np.float32)
        by = np.eye(8, dtype=np.float32)[rng.randint(0, 8, batch)]
        return ex, {x: bx, y_: by}

    def window(introspect):
        ex, feeds = build(introspect)
        for _ in range(warmup):
            ex.run("train", feed_dict=feeds)
        loss = ex.run("train", feed_dict=feeds)[0]
        float(np.mean(loss.asnumpy()))   # drain before the window
        t0 = time.time()
        for _ in range(iters - 1):
            ex.run("train", feed_dict=feeds)
        last = ex.run("train", feed_dict=feeds)[0]
        float(np.mean(last.asnumpy()))   # one sync for the whole window
        return (time.time() - t0) / iters * 1000

    ms_off = window(0)
    scope_mod.shutdown()   # detach the recorder between the A/B arms
    ms_on = window(cadence)
    scope_mod.shutdown()
    return {"step_ms_off": round(ms_off, 4), "step_ms_on": round(ms_on, 4),
            "introspect_overhead_pct": round(
                (ms_on - ms_off) / ms_off * 100, 2),
            "cadence": cadence}


def bench_trail_overhead(batch_size=128, iters=40, rows=5000, width=16,
                         warmup=10, windows=3):
    """hetutrail always-on cost (docs/OBSERVABILITY.md pillar 5 acceptance:
    < 2%/step with the ring enabled): the SAME PS-mode embedding trainer
    against one live cluster, client span ring disarmed vs armed (SetTrail
    A/B on the singleton worker + per-boundary span drain). Interleaved
    best-of-N windows (off/on alternating, min per leg) — run-to-run noise
    on this container (±6%) exceeds the cost being measured, and a
    sequential A/B would land any load drift entirely in the delta.

    Scope caveat: the SERVER-side rings stay armed in both legs (they arm
    from HETU_TRAIL_DIR at spawn; there is no runtime toggle), so the
    delta measures the client ring + drain — the only trail cost on the
    worker's critical path. The server's on-request cost before the
    response is two clock reads (~40 ns); its record+flush run after
    send_msg, off the caller's path."""
    import glob as _glob
    import shutil
    import tempfile
    from hetu_tpu.ps.local_cluster import local_cluster
    tdir = tempfile.mkdtemp(prefix="hetu_trail_bench_")
    saved = os.environ.get("HETU_TRAIL_DIR")
    os.environ["HETU_TRAIL_DIR"] = tdir
    try:
        with local_cluster(n_servers=2, n_workers=1):
            import hetu_tpu as ht

            def build(leg):
                # disjoint server tensor ids per leg (see bench_wdl_ps)
                os.environ["HETU_PS_ID_BASE"] = str(leg * 1000)
                embed = ht.init.random_normal((rows, width), stddev=0.05,
                                              name=f"embed{leg}",
                                              is_embed=True)
                idx = ht.Variable(name="idx", trainable=False)
                y_ = ht.Variable(name="y_", trainable=False)
                vec = ht.embedding_lookup_op(embed, idx)
                flat = ht.array_reshape_op(vec, (-1, 4 * width))
                w = ht.init.random_normal((4 * width, 1), stddev=0.1,
                                          name=f"w{leg}")
                prob = ht.sigmoid_op(ht.matmul_op(flat, w))
                loss = ht.reduce_mean_op(
                    ht.binarycrossentropy_op(prob, y_), [0])
                train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
                ex = ht.Executor({"train": [loss, train_op]},
                                 ctx=ht.cpu(0), comm_mode="Hybrid", seed=0)
                rng = np.random.RandomState(7)
                feeds = {idx: rng.randint(0, rows, (batch_size, 4))
                         .astype(np.float32),
                         y_: rng.randint(0, 2, (batch_size, 1))
                         .astype(np.float32)}
                return ex, feeds

            # leg 1 (env set at build) gets the trail writer; leg 0 is
            # built with the env hidden so its runtime never drains
            os.environ.pop("HETU_TRAIL_DIR", None)
            ex_off, feeds_off = build(0)
            os.environ["HETU_TRAIL_DIR"] = tdir
            ex_on, feeds_on = build(1)

            def window(ex, feeds, armed):
                # re-arm per window: SetTrail state is per-worker (a
                # process singleton), not per-executor. Disarming CLEARS
                # the native ring, so the on-leg's undrained tail must hit
                # its file first or client_spans undercounts.
                if not armed:
                    from hetu_tpu.telemetry import trail as _trail
                    rt = ex_on.ps_runtime
                    if rt.trail_writer is not None:
                        with rt._rpc_lock:
                            _trail.drain_client_spans(rt.comm,
                                                      rt.trail_writer)
                ex.ps_runtime.comm.SetTrail(armed)
                for _ in range(warmup):
                    ex.run("train", feed_dict=feeds)
                t0 = time.time()
                for _ in range(iters - 1):
                    ex.run("train", feed_dict=feeds)
                float(np.mean(ex.run("train",
                                     feed_dict=feeds)[0].asnumpy()))
                return (time.time() - t0) / iters * 1000

            off_windows, on_windows = [], []
            for _ in range(windows):   # interleaved: drift hits both legs
                off_windows.append(window(ex_off, feeds_off, False))
                on_windows.append(window(ex_on, feeds_on, True))
            ms_off, ms_on = min(off_windows), min(on_windows)
            ex_off.close()
            ex_on.close()   # shutdown() drains the ring's tail into the file
            spans = 0
            for p in _glob.glob(os.path.join(tdir,
                                             "trail-client-r*.jsonl")):
                with open(p) as f:
                    spans += sum(1 for line in f if '"kind":"rpc"' in line)
        os.environ.pop("HETU_PS_ID_BASE", None)
        return {"step_ms_off": round(ms_off, 4),
                "step_ms_on": round(ms_on, 4),
                "trail_overhead_pct": round(
                    (ms_on - ms_off) / ms_off * 100, 2),
                "client_spans": spans, "windows": windows}
    finally:
        if saved is None:
            os.environ.pop("HETU_TRAIL_DIR", None)
        else:
            os.environ["HETU_TRAIL_DIR"] = saved
        shutil.rmtree(tdir, ignore_errors=True)


def bench_watch_overhead(width=256, batch=256, iters=40, warmup=None,
                         windows=3, cadence=None):
    """hetuwatch armed cost (docs/OBSERVABILITY.md pillar 6 acceptance:
    <= 2%/step at the default cadence): two identical MLP trainers with
    telemetry AND plan adoption in BOTH arms — the sentinel disarmed vs
    armed — so the delta isolates hetuwatch itself (the residual fold,
    gauge export, SLO latches and the kind:"watch" JSONL row on
    1-in-cadence steps), not the telemetry baseline it rides on.
    Interleaved best-of-N windows (the bench_trail_overhead discipline):
    container noise exceeds the cost being measured, and a sequential A/B
    would land any load drift entirely in the delta. CPU-pinned via
    SECTION_ENV for the same reason."""
    import shutil
    import tempfile
    import hetu_tpu as ht
    from hetu_tpu import telemetry as tel_mod
    from hetu_tpu.graph import executor as ex_mod
    from hetu_tpu.telemetry import watch as watch_mod

    cadence = cadence or watch_mod.DEFAULT_CADENCE
    if warmup is None:
        warmup = cadence + 5   # both arms past compile + one full cadence
    tdir = tempfile.mkdtemp(prefix="hetu_watch_bench_")
    saved = os.environ.get("HETU_TELEMETRY_DIR")
    os.environ["HETU_TELEMETRY_DIR"] = tdir
    try:
        def build(watch):
            x = ht.Variable(name="x", trainable=False)
            y_ = ht.Variable(name="y_", trainable=False)
            h = x
            for i in range(3):
                w = ht.init.random_normal((width, width), stddev=0.05,
                                          name=f"w{i}")
                h = ht.relu_op(ht.matmul_op(h, w))
            wo = ht.init.random_normal((width, 8), stddev=0.05, name="wo")
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_op(ht.matmul_op(h, wo), y_), [0])
            train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
            ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                             seed=0, telemetry="metrics", plan="auto",
                             watch=watch,
                             slo="step_ms<100000" if watch else None)
            rng = np.random.RandomState(0)
            bx = rng.randn(batch, width).astype(np.float32)
            by = np.eye(8, dtype=np.float32)[rng.randint(0, 8, batch)]
            return ex, {x: bx, y_: by}

        ex_off, feeds_off = build(0)
        ex_on, feeds_on = build(cadence)
        assert ex_off.plan_watch is None and ex_on.plan_watch is not None

        def window(ex, feeds):
            for _ in range(warmup):
                ex.run("train", feed_dict=feeds)
            t0 = time.time()
            for _ in range(iters - 1):
                ex.run("train", feed_dict=feeds)
            float(np.mean(ex.run("train",
                                 feed_dict=feeds)[0].asnumpy()))
            return (time.time() - t0) / iters * 1000

        # Direct per-observation stopwatch alongside the A/B: the hook's
        # cost (~0.2 ms) amortized over the cadence is ~0.5% of this
        # container's ~3.7 ms step, BELOW the run-to-run noise an
        # interleaved A/B can resolve here — so record both, headline
        # the amortized number, and keep the A/B as the noise-floor
        # cross-check (the trail cell's 1.3 ms step could resolve its
        # delta; this one cannot).
        observe_ms = []
        orig_observe = ex_mod.SubExecutor._watch_observe

        def timed_observe(self, *a, **k):
            t0 = time.time()
            r = orig_observe(self, *a, **k)
            observe_ms.append((time.time() - t0) * 1000)
            return r

        ex_mod.SubExecutor._watch_observe = timed_observe
        try:
            off_windows, on_windows = [], []
            for _ in range(windows):   # interleaved: drift hits both legs
                off_windows.append(window(ex_off, feeds_off))
                on_windows.append(window(ex_on, feeds_on))
        finally:
            ex_mod.SubExecutor._watch_observe = orig_observe
        ms_off, ms_on = min(off_windows), min(on_windows)
        obs_ms = (sorted(observe_ms)[len(observe_ms) // 2]
                  if observe_ms else 0.0)
        return {"step_ms_off": round(ms_off, 4),
                "step_ms_on": round(ms_on, 4),
                "watch_overhead_pct": round(
                    (ms_on - ms_off) / ms_off * 100, 2),
                "watch_observe_ms": round(obs_ms, 4),
                "watch_amortized_pct": round(
                    obs_ms / cadence / ms_off * 100, 2),
                "cadence": cadence, "windows": windows,
                "observations": ex_on.plan_watch.observations}
    finally:
        tel_mod.shutdown()
        if saved is None:
            os.environ.pop("HETU_TELEMETRY_DIR", None)
        else:
            os.environ["HETU_TELEMETRY_DIR"] = saved
        shutil.rmtree(tdir, ignore_errors=True)


def bench_pilot_overhead(width=64, batch=128, iters=60, warmup=10,
                         windows=4):
    """hetupilot armed-idle cost (docs/FAULT_TOLERANCE.md "Self-tuning
    with guardrails" acceptance: < 1%/step while idle): two identical
    PS-mode dense trainers against ONE live cluster, hetuwatch armed in
    BOTH arms (an SLO the job can never trip, so no recommendation ever
    reaches the controller) — the controller disarmed vs armed — so the
    delta isolates the pilot's steady-state tax: the residual-row feed
    and the per-step boundary walk (governor/pending/verdict checks that
    all fall through). Actuation-era cost is NOT this cell's subject;
    the eras are deliberate, rare, operator-audited events measured by
    tests/test_pilot.py. Interleaved best-of-N windows plus a direct
    stopwatch on Pilot.step_boundary (the watch cell's discipline: the
    cost sits below container noise, so headline the direct reading and
    keep the A/B as the noise-floor cross-check)."""
    import shutil
    import tempfile
    import hetu_tpu as ht
    from hetu_tpu import telemetry as tel_mod
    from hetu_tpu import pilot as pilot_mod
    tdir = tempfile.mkdtemp(prefix="hetu_pilot_bench_")
    saved = {k: os.environ.get(k)
             for k in ("HETU_TELEMETRY_DIR", "HETU_PILOT",
                       "HETU_PILOT_DIR")}
    os.environ["HETU_TELEMETRY_DIR"] = tdir
    os.environ["HETU_PILOT_DIR"] = os.path.join(tdir, "pilot")
    try:
        from hetu_tpu.ps.local_cluster import local_cluster
        with local_cluster(n_servers=1, n_workers=1):
            def build(tag, pilot_on):
                if pilot_on:
                    os.environ["HETU_PILOT"] = "1"
                else:
                    os.environ.pop("HETU_PILOT", None)
                os.environ["HETU_PS_ID_BASE"] = str(tag * 1000)
                x = ht.Variable(name="x", trainable=False)
                y_ = ht.Variable(name="y_", trainable=False)
                w = ht.init.random_normal((width, 8), stddev=0.05,
                                          name=f"w{tag}")
                loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
                    ht.matmul_op(x, w), y_), [0])
                train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
                ex = ht.Executor({"train": [loss, train_op]},
                                 ctx=ht.cpu(0), comm_mode="PS", bsp=True,
                                 prefetch=False, seed=0,
                                 telemetry="metrics", watch=1,
                                 slo="step_ms<100000")
                rng = np.random.RandomState(0)
                bx = rng.randn(batch, width).astype(np.float32)
                by = np.eye(8, dtype=np.float32)[rng.randint(0, 8, batch)]
                return ex, {x: bx, y_: by}

            ex_off, feeds_off = build(1, False)
            ex_on, feeds_on = build(2, True)
            assert ex_off.pilot is None and ex_on.pilot is not None

            def window(ex, feeds):
                for _ in range(warmup):
                    ex.run("train", feed_dict=feeds)
                t0 = time.time()
                for _ in range(iters):
                    ex.run("train", feed_dict=feeds)
                return (time.time() - t0) / iters * 1000

            boundary_ms = []
            orig_boundary = pilot_mod.Pilot.step_boundary

            def timed_boundary(self, *a, **k):
                t0 = time.time()
                r = orig_boundary(self, *a, **k)
                boundary_ms.append((time.time() - t0) * 1000)
                return r

            pilot_mod.Pilot.step_boundary = timed_boundary
            try:
                off_w, on_w = [], []
                for _ in range(windows):   # interleaved: drift hits both
                    off_w.append(window(ex_off, feeds_off))
                    on_w.append(window(ex_on, feeds_on))
            finally:
                pilot_mod.Pilot.step_boundary = orig_boundary
            ms_off, ms_on = min(off_w), min(on_w)
            bd_ms = (sorted(boundary_ms)[len(boundary_ms) // 2]
                     if boundary_ms else 0.0)
            s = pilot_mod.summarize_dir(os.environ["HETU_PILOT_DIR"])
            ex_off.close()
            ex_on.close()
            return {"step_ms_off": round(ms_off, 4),
                    "step_ms_on": round(ms_on, 4),
                    "pilot_overhead_pct": round(
                        (ms_on - ms_off) / ms_off * 100, 2),
                    "pilot_boundary_ms": round(bd_ms, 4),
                    "pilot_amortized_pct": round(bd_ms / ms_off * 100, 2),
                    "eras": (s or {}).get("eras", 0),   # must stay 0
                    "windows": windows}
    finally:
        tel_mod.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tdir, ignore_errors=True)


def bench_story_overhead(width=64, batch=128, iters=4000, warmup=400,
                         windows=6, step_iters=40, step_warmup=8):
    """hetustory run-identity stamping cost (docs/OBSERVABILITY.md pillar
    7 acceptance: < 0.5%/step): every JSONL row a heturun job writes now
    carries (run_id, inc). The pair is merged into the sink's
    PRESERIALIZED base-field prefix at Telemetry construction, so the
    per-record cost is writing ~30 extra bytes, not serializing two extra
    fields per step. A/B on the hot step-record path itself — two
    Telemetry instances, stamped vs not, interleaved best-of-N windows
    (the watch/pilot cell discipline: the cost sits far below container
    noise, so headline the direct per-record reading) — then amortized
    against a real dense training step measured in-process."""
    import shutil
    import tempfile
    import hetu_tpu as ht
    from hetu_tpu import telemetry as tel_mod
    tdir = tempfile.mkdtemp(prefix="hetu_story_bench_")
    saved = {k: os.environ.get(k)
             for k in ("HETU_RUN_ID", "HETU_RUN_INCARNATION")}
    phases = {"compute": 1.1, "ps_pull": 0.2, "ps_push": 0.2}
    try:
        os.environ.pop("HETU_RUN_ID", None)
        tel_off = tel_mod.Telemetry(
            "metrics", os.path.join(tdir, "off"), 0)
        os.environ["HETU_RUN_ID"] = "bench-20260101-000000-1"
        os.environ["HETU_RUN_INCARNATION"] = "1"
        tel_on = tel_mod.Telemetry(
            "metrics", os.path.join(tdir, "on"), 0)

        def window(tel, base):
            for i in range(warmup):
                tel.step_record("train", base + i, 1.234, phases=phases)
            tel.sink.flush()
            t0 = time.time()
            for i in range(iters):
                tel.step_record("train", base + warmup + i, 1.234,
                                phases=phases)
            tel.sink.flush()
            return (time.time() - t0) / iters * 1e6   # us/record

        off_w, on_w = [], []
        for k in range(windows):   # interleaved: drift hits both arms
            base = k * (warmup + iters)
            off_w.append(window(tel_off, base))
            on_w.append(window(tel_on, base))
        us_off, us_on = min(off_w), min(on_w)
        with open(os.path.join(tdir, "off", "metrics-r0.jsonl")) as f:
            row_off = len(f.readline())
        with open(os.path.join(tdir, "on", "metrics-r0.jsonl")) as f:
            row_on = len(f.readline())
        tel_off.close()
        tel_on.close()

        # amortize against a real dense training step on this host
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        w = ht.init.random_normal((width, 8), stddev=0.05, name="w_story")
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(x, w), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         seed=0)
        rng = np.random.RandomState(0)
        feeds = {x: rng.randn(batch, width).astype(np.float32),
                 y_: np.eye(8, dtype=np.float32)[
                     rng.randint(0, 8, batch)]}
        for _ in range(step_warmup):
            ex.run("train", feed_dict=feeds)
        t0 = time.time()
        for _ in range(step_iters):
            ex.run("train", feed_dict=feeds)
        ref_step_ms = (time.time() - t0) / step_iters * 1000
        ex.close()
        return {"record_us_off": round(us_off, 3),
                "record_us_on": round(us_on, 3),
                "row_bytes_off": row_off, "row_bytes_on": row_on,
                "ref_step_ms": round(ref_step_ms, 4),
                "story_overhead_pct": round(
                    max(0.0, us_on - us_off) / 1000 / ref_step_ms * 100,
                    4),
                "windows": windows}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tdir, ignore_errors=True)


def bench_chaos_hardening(batch_size=128, iters=60, rows=5000, width=16,
                          warmup=10, windows=8):
    """hetuchaos transport-hardening cost (docs/FAULT_TOLERANCE.md
    acceptance: retry/CRC hardening <= 2%/step): the SAME PS-mode
    embedding trainer against one live cluster, CRC32C payload checksums
    off vs on (SetPsCrc A/B on the singleton worker — the kFlagCrc
    negotiation means one client-side toggle flips BOTH legs: request
    verify on the server and response checksum back). Interleaved
    best-of-N windows, min per leg — same noise reasoning as the trail
    cell. The retry/backoff machinery itself costs nothing on a clean
    wire (it only runs after a failure), so CRC compute IS the
    hardening's steady-state price; the cell also records that zero
    retries/rejects happened, pinning that the measured delta is pure
    checksum arithmetic."""
    from hetu_tpu.ps.local_cluster import local_cluster
    with local_cluster(n_servers=2, n_workers=1):
        import hetu_tpu as ht
        embed = ht.init.random_normal((rows, width), stddev=0.05,
                                      name="embed_crc", is_embed=True)
        idx = ht.Variable(name="idx", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        vec = ht.embedding_lookup_op(embed, idx)
        flat = ht.array_reshape_op(vec, (-1, 4 * width))
        w = ht.init.random_normal((4 * width, 1), stddev=0.1, name="w_crc")
        prob = ht.sigmoid_op(ht.matmul_op(flat, w))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode="Hybrid", seed=0)
        rng = np.random.RandomState(7)
        feeds = {idx: rng.randint(0, rows, (batch_size, 4))
                 .astype(np.float32),
                 y_: rng.randint(0, 2, (batch_size, 1)).astype(np.float32)}
        comm = ex.ps_runtime.comm

        def window(crc_on):
            comm.SetPsCrc(crc_on)
            for _ in range(warmup):
                ex.run("train", feed_dict=feeds)
            t0 = time.time()
            for _ in range(iters - 1):
                ex.run("train", feed_dict=feeds)
            float(np.mean(ex.run("train", feed_dict=feeds)[0].asnumpy()))
            return (time.time() - t0) / iters * 1000

        off_w, on_w = [], []
        for _ in range(windows):   # interleaved: drift hits both legs
            off_w.append(window(False))
            on_w.append(window(True))
        ms_off, ms_on = min(off_w), min(on_w)
        cs = comm.ClientStats()
        ex.close()
        return {"step_ms_off": round(ms_off, 4),
                "step_ms_on": round(ms_on, 4),
                "crc_overhead_pct": round((ms_on - ms_off) / ms_off * 100,
                                          2),
                # a clean wire: the delta above is checksum math, not
                # retry noise (nonzero here would invalidate the A/B)
                "retries": cs["retries"], "crc_rejects": cs["crc_rejects"],
                "windows": windows}


def bench_snapshot_overhead(batch_size=128, iters=200, rows=5000, width=16,
                            warmup=10, windows=4, snap_every=200):
    """hetusave coordinated-snapshot cost (docs/FAULT_TOLERANCE.md
    acceptance: snapshot stall < 5%/step amortized at the measured
    cadence): the SAME PS-mode embedding trainer against one live
    cluster, with leg B taking a full coordinated job snapshot (quiesce
    barrier + per-server kSnapshotNow + worker pickle + manifest commit)
    every ``snap_every`` steps — the stall is the AMORTIZED per-step
    delta, the number an operator actually pays. Interleaved best-of-N
    windows, min per leg, same noise reasoning as the trail/chaos cells.
    The raw wall time of one snapshot is also reported (from the last
    committed manifest), so the amortization arithmetic is auditable:
    stall% ~= snapshot_wall_ms / (snap_every * step_ms)."""
    import shutil
    import tempfile
    from hetu_tpu.recovery import latest_committed_manifest, \
        take_job_snapshot
    snaproot = tempfile.mkdtemp(prefix="bench_snap_")
    jobdir = tempfile.mkdtemp(prefix="bench_snapjob_")
    saved = os.environ.get("DMLC_PS_SNAPSHOT_DIR")
    os.environ["DMLC_PS_SNAPSHOT_DIR"] = snaproot
    try:
        from hetu_tpu.ps.local_cluster import local_cluster
        with local_cluster(n_servers=2, n_workers=1):
            import hetu_tpu as ht
            embed = ht.init.random_normal((rows, width), stddev=0.05,
                                          name="embed_snap", is_embed=True)
            idx = ht.Variable(name="idx", trainable=False)
            y_ = ht.Variable(name="y_", trainable=False)
            vec = ht.embedding_lookup_op(embed, idx)
            flat = ht.array_reshape_op(vec, (-1, 4 * width))
            w = ht.init.random_normal((4 * width, 1), stddev=0.1,
                                      name="w_snap")
            prob = ht.sigmoid_op(ht.matmul_op(flat, w))
            loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_),
                                     [0])
            train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
            ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                             comm_mode="PS", seed=0, prefetch=False)
            rng = np.random.RandomState(7)
            feeds = {idx: rng.randint(0, rows, (batch_size, 4))
                     .astype(np.float32),
                     y_: rng.randint(0, 2, (batch_size, 1))
                     .astype(np.float32)}

            def window(snap_on):
                for _ in range(warmup):
                    ex.run("train", feed_dict=feeds)
                n = 0
                t0 = time.time()
                for i in range(iters):
                    ex.run("train", feed_dict=feeds)
                    if snap_on and (i + 1) % snap_every == 0:
                        take_job_snapshot(ex, jobdir)
                        n += 1
                return (time.time() - t0) / iters * 1000, n

            off_w, on_w, n_snaps = [], [], 0
            for _ in range(windows):   # interleaved: drift hits both legs
                off_w.append(window(False)[0])
                ms, n = window(True)
                on_w.append(ms)
                n_snaps += n
            ms_off, ms_on = min(off_w), min(on_w)
            got = latest_committed_manifest(jobdir)
            snap_ms = float(got[0].get("wall_ms", -1)) if got else -1.0
            ex.close()
            return {"step_ms_off": round(ms_off, 4),
                    "step_ms_on": round(ms_on, 4),
                    "snapshot_stall_pct": round(
                        (ms_on - ms_off) / ms_off * 100, 2),
                    "snapshot_wall_ms": round(snap_ms, 3),
                    "snap_every": snap_every, "snapshots": n_snaps,
                    "windows": windows}
    finally:
        if saved is None:
            os.environ.pop("DMLC_PS_SNAPSHOT_DIR", None)
        else:
            os.environ["DMLC_PS_SNAPSHOT_DIR"] = saved
        shutil.rmtree(snaproot, ignore_errors=True)
        shutil.rmtree(jobdir, ignore_errors=True)


def _capture_trace(out, step_twice, trace_dir, label):
    """Post-window jax.profiler capture shared by the LM cells (bert,
    transformer/350): runs AFTER the timed window so tracing overhead
    never pollutes the reported step time. An explicit ``trace_dir`` is
    used as-is; the HETU_BENCH_TRACE env dir gains a per-section
    ``label`` subdir so each cell's flame graph stays attributable."""
    if not trace_dir:
        env = os.environ.get("HETU_BENCH_TRACE")
        trace_dir = os.path.join(env, label) if env else None
    if not trace_dir:
        return
    import jax.profiler
    with jax.profiler.trace(trace_dir):
        step_twice()
    out["trace"] = trace_dir
    # counted in-child: smoke trace dirs are TemporaryDirectories deleted
    # when the section exits, so "did the trace land" must be recorded
    # before cleanup (tests/test_bench_sections.py asserts on it)
    out["trace_files"] = sum(len(fs) for _, _, fs in os.walk(trace_dir))


def bench_bert(batch_size=32, seq_len=512, warmup=3, iters=15, cfg=None,
               trace_dir=None, **cfg_overrides):
    """BERT-base MLM+NSP pretrain step (BASELINE.md north star: 'BERT-base
    pretrain (Pallas attention)'). Dense packed batches -> the fused
    bidirectional flash kernel; tokens/s with BOTH the 6ND and the
    attention-inclusive MFU."""
    import jax
    from hetu_tpu.models import bert

    if cfg is None:
        cfg = bert.BERT_BASE
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    n_params = bert.count_params(params)
    opt = bert.init_opt_state(params)
    step = bert.make_pretrain_step(cfg, mesh=None, lr=1e-4)
    rng = np.random.RandomState(0)
    P = 76  # ~15% of 512
    batch = {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch_size, seq_len)).astype(np.int32),
        "segment_ids": (rng.rand(batch_size, seq_len) > 0.5).astype(np.int32),
        "mlm_positions": np.sort(rng.randint(
            1, seq_len, (batch_size, P)).astype(np.int32), axis=1),
        "mlm_ids": rng.randint(0, cfg.vocab_size,
                               (batch_size, P)).astype(np.int32),
        "mlm_weights": np.ones((batch_size, P), np.float32),
        "nsp_label": rng.randint(0, 2, (batch_size,)).astype(np.int32),
    }
    def timed(params, opt, batch, n_warm):
        """Warmup then one timing window closed by a host read of the
        loss: one transfer per window, not per step."""
        loss = None
        for _ in range(n_warm):
            loss, _, params, opt = step(params, opt, batch)
        float(np.asarray(loss))
        t0 = time.time()
        for _ in range(iters):
            loss, _, params, opt = step(params, opt, batch)
        float(np.asarray(loss))
        return (time.time() - t0) / iters, params, opt

    dt, params, opt = timed(params, opt, batch, warmup)
    tokens = batch_size * seq_len
    flops_6nd = 6.0 * n_params * tokens
    flops_attn = _attn_flops(batch_size, seq_len, cfg.n_layers, cfg.d_model,
                             causal=False)
    from hetu_tpu.models import transformer as tfm
    impl = tfm._resolve_attn_impl(cfg.trunk(), None, seq_len)
    from hetu_tpu.kernels.fused_ce import should_fuse
    fused_ce = should_fuse(cfg.fused_mlm_ce, None)
    out = {"tokens_per_sec": round(tokens / dt, 0),
           "step_ms": round(dt * 1000, 2),
           "mfu_6nd": _mfu(flops_6nd, dt),
           "mfu_attn_incl": _mfu(flops_6nd + flops_attn, dt),
           "attn_impl": impl,
           "mlm_ce": "fused" if fused_ce else "einsum",
           "n_params": n_params}

    def _two_steps():
        nonlocal params, opt
        loss = None
        for _ in range(2):
            loss, _, params, opt = step(params, opt, batch)
        float(np.asarray(loss))

    _capture_trace(out, _two_steps, trace_dir, "bert")

    # masked A/B: padded batches keep the fused kernel via the key-padding
    # bias (before round 4 a mask forced the unfused (B,nh,T,T) path)
    batch["input_mask"] = (
        np.arange(seq_len)[None, :]
        < rng.randint(seq_len // 2, seq_len + 1, (batch_size, 1))
    ).astype(np.int32)
    dtm, params, opt = timed(params, opt, batch, max(1, warmup - 1))
    bias = jax.numpy.zeros((batch_size, 1, 1, seq_len))
    out["masked"] = {
        "tokens_per_sec": round(tokens / dtm, 0),
        "step_ms": round(dtm * 1000, 2),
        "attn_impl": tfm._resolve_attn_impl(cfg.trunk(), None, seq_len, bias),
    }
    return out


def bench_flash_attention(b=4, h=8, s=4096, d=64, iters=10):
    """Pallas flash kernels vs the unfused reference form at seq 4096
    (fwd and full grad, bf16, hard-synced) — the long-context headline."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.kernels.flash_attention import flash_attention, mha_reference

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
               for _ in range(3))

    out = {}
    for name, fn in (("flash", flash_attention), ("unfused", mha_reference)):
        fwd = jax.jit(lambda q, k, v, f=fn: f(q, k, v, True))
        grad = jax.jit(jax.grad(
            lambda q, k, v, f=fn: jnp.sum(f(q, k, v, True)
                                          .astype(jnp.float32)),
            argnums=(0, 1, 2)))
        float(np.asarray(fwd(q, k, v)[0, 0, 0, 0]))   # compile + sync
        t0 = time.time()
        for _ in range(iters):
            o = fwd(q, k, v)
        float(np.asarray(o[0, 0, 0, 0]))
        fwd_ms = (time.time() - t0) / iters * 1000
        g = grad(q, k, v)
        float(np.asarray(g[0][0, 0, 0, 0]))           # compile + sync
        t0 = time.time()
        for _ in range(iters):
            g = grad(q, k, v)
        float(np.asarray(g[0][0, 0, 0, 0]))
        out[name] = {"fwd_ms": round(fwd_ms, 2),
                     "grad_ms": round((time.time() - t0) / iters * 1000, 2)}
    out["fwd_speedup"] = round(
        out["unfused"]["fwd_ms"] / out["flash"]["fwd_ms"], 2)
    out["grad_speedup"] = round(
        out["unfused"]["grad_ms"] / out["flash"]["grad_ms"], 2)
    return out


def bench_decode(batch=8, prompt_len=16, max_len=256):
    """KV-cache greedy decode throughput on the 38M flagship (inference
    side of the north star; one compiled scan, hard-synced)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm
    from hetu_tpu.models import generate as gen

    cfg = tfm.TransformerConfig(vocab_size=8192, d_model=512, n_heads=8,
                                n_layers=8, d_ff=2048, max_seq_len=512)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    fn = gen.make_generate_fn(cfg, max_len=max_len)
    prompt = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    toks, _ = fn(params, prompt, jax.random.PRNGKey(0))   # compile
    np.asarray(toks)
    t0 = time.time()
    toks, _ = fn(params, prompt, jax.random.PRNGKey(1))
    np.asarray(toks)
    dt = time.time() - t0
    new_tokens = batch * (max_len - prompt_len)
    return new_tokens / dt, dt / (max_len - prompt_len) * 1000


def bench_transformer(cfg=None, batch=16, seq=512, warmup=3, iters=20,
                      trace_dir=None, trace_label="transformer",
                      **cfg_overrides):
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm

    if cfg is None:
        cfg = tfm.TransformerConfig(vocab_size=8192, d_model=512, n_heads=8,
                                    n_layers=8, d_ff=2048, max_seq_len=512)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, mesh=None, lr=3e-4)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    tgt = jnp.roll(tok, -1, axis=1)
    for _ in range(warmup):
        loss, params, opt = step(params, opt, tok, tgt)
    float(np.asarray(loss))   # hard sync (see bench_bert)
    t0 = time.time()
    for _ in range(iters):
        loss, params, opt = step(params, opt, tok, tgt)
    float(np.asarray(loss))
    dt = (time.time() - t0) / iters
    tokens = batch * seq
    # 6ND: fwd+bwd matmul flops for a decoder-only transformer; the
    # attention-inclusive denominator adds the T^2-scaling score matmuls
    flops_6nd = 6.0 * n_params * tokens
    flops_attn = _attn_flops(batch, seq, cfg.n_layers, cfg.d_model,
                             causal=True)
    out = {"tokens_per_sec": round(tokens / dt, 0),
           "step_ms": round(dt * 1000, 2),
           "mfu_6nd": _mfu(flops_6nd, dt),
           "mfu_attn_incl": _mfu(flops_6nd + flops_attn, dt),
           "attn_impl": tfm._resolve_attn_impl(cfg, None, seq),
           "n_params": n_params}
    def _two_steps():
        nonlocal params, opt
        loss = None
        for _ in range(2):
            loss, params, opt = step(params, opt, tok, tgt)
        float(np.asarray(loss))

    _capture_trace(out, _two_steps, trace_dir, trace_label)
    return out


# ---------------------------------------------------------------------------
# WDL-Criteo through a real local PS cluster (BASELINE.md sparse north star):
# scheduler + 2 server processes over loopback, this process as the worker,
# comm_mode='Hybrid' (dense grads on-device, embedding rows through the PS).
# ---------------------------------------------------------------------------

def bench_wdl_ps(batch_size=128, warmup=5, iters=40, feature_dim=100000):
    """Returns {prefetch_on: (sps, ms, perf), prefetch_off: (sps, ms)} — the
    overlap A/B the reference's prefetch x ASP matrix is about."""
    from hetu_tpu.ps.local_cluster import local_cluster
    with local_cluster(n_servers=2, n_workers=1):
        import hetu_tpu as ht
        models = _import_models("ctr")
        from models.load_data import load_criteo_data

        (tr_dense, tr_sparse, tr_y), _ = load_criteo_data(
            feature_dimension=feature_dim, n_train=batch_size * 8, n_test=64)

        out = {}
        for leg, prefetch in enumerate((True, False)):
            # disjoint server tensor ids per leg: the servers are live across
            # both legs and ParamInit is idempotent, so reusing ids would
            # resume from the first leg's trained values
            os.environ["HETU_PS_ID_BASE"] = str(leg * 1000)
            dense = ht.dataloader_op([ht.Dataloader(tr_dense, batch_size,
                                                    "train")])
            sparse = ht.dataloader_op([ht.Dataloader(tr_sparse, batch_size,
                                                     "train")])
            y_ = ht.dataloader_op([ht.Dataloader(tr_y, batch_size, "train")])
            loss, y, labels, train_op = models.wdl_criteo(
                dense, sparse, y_, feature_dimension=feature_dim,
                embedding_size=16)
            ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.tpu(0),
                             comm_mode="Hybrid", seed=0, prefetch=prefetch)
            for _ in range(warmup):
                ex.run("train")
            float(np.mean(ex.run("train")[0].asnumpy()))
            t0 = time.time()
            for _ in range(iters - 1):
                ex.run("train")
            float(np.mean(ex.run("train")[0].asnumpy()))
            dt = (time.time() - t0) / iters
            key = "prefetch_on" if prefetch else "prefetch_off"
            out[key] = {"samples_per_sec": round(batch_size / dt, 1),
                        "step_ms": round(dt * 1000, 2)}
            if prefetch:
                ex.ps_runtime.drain()
                out[key]["ps_perf"] = dict(ex.ps_runtime.perf)
            ex.close()
        os.environ.pop("HETU_PS_ID_BASE", None)
        return out


# ---------------------------------------------------------------------------
# hetuq (docs/COMM_QUANT.md): quantized-communication A/B cells. Both are
# framework-relative measurements pinned to the CPU backend (SECTION_ENV) —
# the PS cell's bytes-on-wire counters and AUC delta and the DP cell's
# loss deltas are device-independent, and the CPU run is deterministic.
# ---------------------------------------------------------------------------

def bench_comm_quant_ps(batch_size=128, steps=1000, feature_dim=10000,
                        embedding_size=32, n_test=1024, warmup=5,
                        n_train=8192, learning_rate=0.02, stddev=0.1):
    """WDL-Criteo under comm_mode='PS' (dense AND sparse params PS-hosted),
    quant off vs int8: bytes-on-wire from the worker's raw/wire counters
    (client_stats), step time, and final test AUC per leg. The acceptance
    claim — >=3x wire reduction at AUC within 0.002 — is measured here.
    lr/stddev are tuned so BOTH legs converge well clear of the synthetic
    task's steep learning-curve transition — reading AUC mid-transition
    would measure noise-shifted timing, not quality."""
    from hetu_tpu.ps.local_cluster import local_cluster
    with local_cluster(n_servers=2, n_workers=1):
        import hetu_tpu as ht
        from hetu_tpu import metrics as ht_metrics
        models = _import_models("ctr")
        from models.load_data import load_criteo_data

        (tr_dense, tr_sparse, tr_y), (te_dense, te_sparse, te_y) = \
            load_criteo_data(feature_dimension=feature_dim,
                             n_train=n_train, n_test=n_test)
        out = {}
        for leg, mode in enumerate(("off", "int8")):
            # disjoint server tensor ids per leg (see bench_wdl_ps)
            os.environ["HETU_PS_ID_BASE"] = str(leg * 1000)
            dense = ht.dataloader_op([
                ht.Dataloader(tr_dense, batch_size, "train"),
                ht.Dataloader(te_dense, batch_size, "validate")])
            sparse = ht.dataloader_op([
                ht.Dataloader(tr_sparse, batch_size, "train"),
                ht.Dataloader(te_sparse, batch_size, "validate")])
            y_ = ht.dataloader_op([
                ht.Dataloader(tr_y, batch_size, "train"),
                ht.Dataloader(te_y, batch_size, "validate")])
            loss, y, labels, train_op = models.wdl_criteo(
                dense, sparse, y_, feature_dimension=feature_dim,
                embedding_size=embedding_size, learning_rate=learning_rate,
                stddev=stddev)
            ex = ht.Executor({"train": [loss, train_op],
                              "validate": [loss, y, y_]}, ctx=ht.cpu(0),
                             comm_mode="PS", seed=0, comm_quant=mode)
            comm = ex.ps_runtime.comm
            for _ in range(warmup):
                ex.run("train")
            float(np.mean(ex.run("train")[0].asnumpy()))  # drain
            cs0 = comm.ClientStats()
            t0 = time.time()
            for _ in range(steps - 1):
                ex.run("train")
            float(np.mean(ex.run("train")[0].asnumpy()))
            dt = (time.time() - t0) / steps
            ex.ps_runtime.drain()
            cs1 = comm.ClientStats()
            preds, labs = [], []
            for _ in range(n_test // batch_size):
                _, yv, lv = ex.run("validate", convert_to_numpy_ret_vals=True)
                preds.append(yv)
                labs.append(lv)
            auc = float(ht_metrics.auc(np.concatenate(labs),
                                       np.concatenate(preds)))
            out[mode] = {
                "step_ms": round(dt * 1000, 2),
                "auc": round(auc, 4),
                "raw_bytes": cs1["quant_raw_bytes"] - cs0["quant_raw_bytes"],
                "wire_bytes": (cs1["quant_wire_bytes"]
                               - cs0["quant_wire_bytes"]),
            }
            ex.close()
        os.environ.pop("HETU_PS_ID_BASE", None)
        # wire reduction = identical logical traffic (same model, steps,
        # batches, seed) at each leg's wire encoding
        out["bytes_wire_ratio"] = round(
            out["off"]["wire_bytes"] / max(1, out["int8"]["wire_bytes"]), 2)
        out["auc_off"] = out["off"]["auc"]
        out["auc_int8"] = out["int8"]["auc"]
        out["auc_delta"] = round(abs(out["off"]["auc"]
                                     - out["int8"]["auc"]), 4)
        return out


def bench_comm_quant_dp(width=512, batch=512, steps=40, warmup=5):
    """DP AllReduce on the 8-device virtual mesh: off vs int8 vs fp8 (same
    seed/feeds), step time + final loss per mode, plus the analytic
    raw-vs-wire ratio of the quantized decomposition (the executor's
    comm_quant_report; the reduce-scatter half stays f32 by construction —
    docs/COMM_QUANT.md)."""
    import hetu_tpu as ht
    from hetu_tpu.comm_quant import fp8_dtype
    from hetu_tpu.utils import ensure_devices

    ensure_devices(8)
    rng = np.random.RandomState(0)
    bx = rng.randn(batch, width).astype(np.float32)
    by = np.eye(8, dtype=np.float32)[rng.randint(0, 8, batch)]

    def run(mode):
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        h = x
        for i in range(3):
            w = ht.init.random_normal((width, width), stddev=0.05,
                                      name=f"w{i}")
            h = ht.relu_op(ht.matmul_op(h, w))
        wo = ht.init.random_normal((width, 8), stddev=0.05, name="wo")
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, wo), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode="AllReduce", seed=0, comm_quant=mode)
        feeds = {x: bx, y_: by}
        for _ in range(warmup):
            ex.run("train", feed_dict=feeds)
        float(np.mean(ex.run("train", feed_dict=feeds)[0].asnumpy()))
        t0 = time.time()
        for _ in range(steps - 1):
            ex.run("train", feed_dict=feeds)
        last = ex.run("train", feed_dict=feeds)[0]
        final = float(np.mean(last.asnumpy()))
        dt = (time.time() - t0) / steps
        return {"step_ms": round(dt * 1000, 2),
                "final_loss": round(final, 6)}, ex.comm_quant_report

    out = {}
    report = None
    modes = ["off", "int8"] + (["fp8"] if fp8_dtype() is not None else [])
    for mode in modes:
        out[mode], rep = run(mode)
        report = rep or report
    if fp8_dtype() is None:
        out["fp8"] = {"error": "float8_e4m3fn unavailable in this jax build"}
    if report:
        out["wire_report"] = report
    out["final_loss_off"] = out["off"]["final_loss"]
    out["loss_delta_int8"] = round(
        abs(out["int8"]["final_loss"] - out["off"]["final_loss"]), 6)
    if "final_loss" in out.get("fp8", {}):
        out["loss_delta_fp8"] = round(
            abs(out["fp8"]["final_loss"] - out["off"]["final_loss"]), 6)
    return out


def bench_planner(width=256, target_width=512, batch=256, warmup=8,
                  iters=40):
    """hetuplan cell (docs/ANALYSIS.md "Tier C: planning"): predicted vs
    measured step time — the acceptance check that the cost model's
    numbers mean something. A CALIBRATION MLP (``width``) trains on CPU
    with telemetry=metrics; its telemetry dir calibrates the planner
    (measured critical-path legs → compute residual + host term, exactly
    what ``hetulint --plan --calibrate`` does). The calibrated model then
    predicts a DIFFERENT graph — the ``target_width`` MLP it has never
    seen — and that graph is trained and measured for the residual. Same-
    graph prediction would be circular (the calibration reproduces its own
    run by construction); cross-size is the real claim. The uncalibrated
    prediction is recorded too — against TPU-assumed peaks on a CPU host
    it is orders of magnitude off BY DESIGN (docs/ROOFLINE.md:
    assumptions, not readings). SECTION_ENV pins the cell to CPU."""
    import tempfile
    import hetu_tpu as ht
    from hetu_tpu import analysis
    from hetu_tpu import telemetry as tel_mod
    from hetu_tpu.telemetry import profiler as prof_mod

    def build(w):
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        h = x
        for i in range(3):
            wt = ht.init.random_normal((w, w), stddev=0.05,
                                       name=f"pw{i}_{w}")
            h = ht.relu_op(ht.matmul_op(h, wt))
        wo = ht.init.random_normal((w, 8), stddev=0.05, name=f"pwo_{w}")
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, wo), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        rng = np.random.RandomState(0)
        feeds = {x: rng.randn(batch, w).astype(np.float32),
                 y_: np.eye(8, dtype=np.float32)[rng.randint(0, 8, batch)]}
        return {"train": [loss, train_op]}, feeds

    def run_measured(graph, feeds, tel_dir):
        os.environ["HETU_TELEMETRY_DIR"] = tel_dir
        ex = ht.Executor(graph, ctx=ht.cpu(0), seed=0, telemetry="metrics")
        for _ in range(warmup):
            ex.run("train", feed_dict=feeds)
        t0 = time.time()
        for _ in range(iters - 1):
            ex.run("train", feed_dict=feeds)
        last = ex.run("train", feed_dict=feeds)[0]
        float(np.mean(last.asnumpy()))   # one sync closes the window
        wall_ms = (time.time() - t0) / iters * 1000
        tel_mod.shutdown()               # flush the step records
        means = prof_mod.step_phase_means(
            prof_mod.read_metrics_records(tel_dir))
        return means.get("step_ms", wall_ms), means

    # calibration run (width) -> measured legs + residuals
    cal_graph, cal_feeds = build(width)
    cal_dir = tempfile.mkdtemp(prefix="hetu_plan_cal_")
    _cal_ms, _ = run_measured(cal_graph, cal_feeds, cal_dir)

    # target run (target_width): predict FIRST, measure after. The
    # calibration carries the CALIBRATION graph's own predicted compute
    # as the residual baseline, so the correction is a true ratio that
    # extrapolates across sizes instead of echoing the measured step.
    cal = analysis.load_calibration(cal_dir)
    cal_baseline = analysis.plan_graph(cal_graph, devices=1,
                                       feed_meta=dict(cal_feeds))
    cal.baseline_compute_ms = cal_baseline.breakdown.get("compute_ms")
    tgt_graph, tgt_feeds = build(target_width)
    feed_meta = dict(tgt_feeds)
    plan_uncal = analysis.plan_graph(tgt_graph, devices=1,
                                     feed_meta=feed_meta)
    plan = analysis.plan_graph(tgt_graph, devices=1, calibrate=cal,
                               feed_meta=feed_meta)
    predicted = plan.predicted_step_ms
    tgt_dir = tempfile.mkdtemp(prefix="hetu_plan_tgt_")
    measured_ms, means = run_measured(tgt_graph, tgt_feeds, tgt_dir)
    err_pct = abs(predicted - measured_ms) / measured_ms * 100 \
        if measured_ms else None
    return {
        "calib_width": width, "target_width": target_width,
        "calib_step_ms": round(_cal_ms, 4),
        "measured_step_ms": round(measured_ms, 4),
        "predicted_step_ms": round(predicted, 4),
        "predicted_uncal_ms": round(plan_uncal.predicted_step_ms, 6),
        "plan_err_pct": round(err_pct, 2) if err_pct is not None else None,
        "plan_comm_mode": plan.comm_mode or "none",
        "plan_mesh": plan.mesh,
        "steps_measured": int(means.get("n_steps", iters)),
    }


def bench_kernels(vocab=1_000_000, dim=32, batch=4096, lookups=4,
                  warmup=5, iters=30):
    """hetukern cell (docs/KERNELS.md): (a) the per-kernel interpret-mode
    equality smoke — force-mode Pallas vs the XLA fallback through the
    real registry dispatch, under jit so both sides compile — and (b) the
    fused-embed-grad A/B on the CTR shape: the pre-hetukern dense
    ``(vocab, dim)`` zeros-table scatter vs the compact rows path
    (sort/unique + segment-sum), step time AND compiled peak HBM from the
    same executable handles hetuprof reads. The structural win (no
    table-sized intermediate) is backend-independent; SECTION_ENV pins the
    cell to CPU so the number is deterministic."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.kernels import registry, embed_grad, csr_spmm, \
        quant_comm, fused_opt
    from hetu_tpu import comm_quant

    rng = np.random.RandomState(0)
    out = {"equality": {}}

    # -- (a) registry dispatch + one equality check per kernel -------------
    def check(name, force_fn, oracle_fn, *args, exact=False, atol=1e-4):
        @jax.jit
        def _force(*a):
            with registry.active("force"):
                return force_fn(*a)

        @jax.jit
        def _off(*a):
            with registry.active("off"):
                return oracle_fn(*a)

        got = jax.tree.map(np.asarray, _force(*args))
        want = jax.tree.map(np.asarray, _off(*args))
        flat_g = jax.tree.leaves(got)
        flat_w = jax.tree.leaves(want)
        # structure must match too — zip would silently truncate a
        # mismatched tree and report a never-checked equivalence
        ok = len(flat_g) == len(flat_w) and all(
            (np.array_equal(a, b) if exact
             else np.allclose(a, b, atol=atol))
            for a, b in zip(flat_g, flat_w))
        out["equality"][name] = "ok" if ok else "MISMATCH"
        return ok

    ev = jnp.asarray(rng.randn(256, 128).astype(np.float32))
    ei = jnp.asarray(rng.randint(0, 40, 256))
    check("fused_embed_grad",
          lambda v, i: embed_grad.embed_grad_rows(v, i, 1000),
          lambda v, i: embed_grad.embed_grad_rows(v, i, 1000), ev, ei)
    sv = jnp.asarray(rng.randn(300).astype(np.float32))
    sr = jnp.asarray(rng.randint(0, 8, 300).astype(np.int32))
    sc = jnp.asarray(rng.randint(0, 16, 300).astype(np.int32))
    sb = jnp.asarray(rng.randn(16, 128).astype(np.float32))
    check("csr_spmm",
          lambda v, r, c, b: csr_spmm.coo_matmat(v, r, c, 8, b),
          lambda v, r, c, b: csr_spmm.coo_matmat(v, r, c, 8, b),
          sv, sr, sc, sb)
    qx = jnp.asarray(rng.randn(4096).astype(np.float32))
    check("quant_blocks",
          lambda x: quant_comm.quantize_blocks(x, 256, "int8"),
          lambda x: comm_quant.quantize_blocks(x, 256, "int8"),
          qx, exact=True)   # wire payloads must be bit-identical
    qq, qs, qn = comm_quant.quantize_blocks(qx, 256, "int8")
    check("dequant_blocks",
          lambda q, s: quant_comm.dequantize_blocks(q, s, 4096, 256),
          lambda q, s: comm_quant.dequantize_blocks(q, s, 4096, 256),
          qq, qs, exact=True)

    class _O:
        beta1, beta2, epsilon, weight_decay, l2reg = 0.9, 0.999, 1e-7, 0.0, 0.0

    op_ = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    og = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    slot = {"m": jnp.zeros((8, 128), jnp.float32),
            "v": jnp.zeros((8, 128), jnp.float32),
            "t": jnp.zeros((), jnp.float32)}
    check("fused_adam",
          lambda p, g: fused_opt.adam_step(_O, p, g, slot, 0.01),
          lambda p, g: fused_opt.adam_step(_O, p, g, slot, 0.01),
          op_, og, exact=True)
    check("fused_sgd",
          lambda p, g: fused_opt.sgd_step(_O, p, g, 0.01),
          lambda p, g: fused_opt.sgd_step(_O, p, g, 0.01),
          op_, og, exact=True)

    # -- (b) fused embed-grad A/B on the CTR shape -------------------------
    # lookups-per-example x batch row grads into a (vocab, dim) table: the
    # dense path writes the whole table per step to carry ~batch live rows
    vec = jnp.asarray(rng.randn(batch, lookups, dim).astype(np.float32))
    idx = jnp.asarray(
        # duplicate-heavy, like CTR hash features (power-law-ish)
        (rng.zipf(1.3, size=(batch, lookups)) % vocab).astype(np.int64))

    dense_fn = jax.jit(
        lambda v, i: embed_grad.embed_grad_dense_xla(v, i, (vocab, dim)))
    rows_fn = jax.jit(
        lambda v, i: embed_grad.embed_grad_rows(v, i, vocab))

    def timed(fn):
        # AOT: compile ONCE and reuse the executable for both the timing
        # loop and memory_analysis (a fresh .lower().compile() after the
        # timed calls would recompile the whole program a second time)
        exe = fn.lower(vec, idx).compile()
        jax.block_until_ready(exe(vec, idx))
        for _ in range(warmup):
            jax.block_until_ready(exe(vec, idx))
        t0 = time.time()
        for _ in range(iters):
            r = exe(vec, idx)
        jax.block_until_ready(r)
        ms = (time.time() - t0) / iters * 1000
        mem = None
        try:
            ma = exe.memory_analysis()
            mem = (int(ma.argument_size_in_bytes)
                   + int(ma.output_size_in_bytes)
                   + int(ma.temp_size_in_bytes)
                   - int(getattr(ma, "alias_size_in_bytes", 0) or 0))
        except Exception:  # noqa: BLE001 — backend may expose no analysis
            pass
        return ms, mem

    ms_dense, mem_dense = timed(dense_fn)
    ms_rows, mem_rows = timed(rows_fn)
    out["embed_grad"] = {
        "vocab": vocab, "dim": dim, "rows_pushed": batch * lookups,
        "dense_step_ms": round(ms_dense, 3),
        "rows_step_ms": round(ms_rows, 3),
        "speedup_rows": round(ms_dense / ms_rows, 2) if ms_rows else None,
    }
    if mem_dense and mem_rows:
        out["embed_grad"]["dense_peak_mib"] = round(mem_dense / 2**20, 2)
        out["embed_grad"]["rows_peak_mib"] = round(mem_rows / 2**20, 2)
        out["embed_grad"]["hbm_ratio"] = round(mem_dense / mem_rows, 2)
    # headline copies for the telemetry line / gate
    out["dense_step_ms"] = out["embed_grad"]["dense_step_ms"]
    out["rows_step_ms"] = out["embed_grad"]["rows_step_ms"]
    out["speedup_rows"] = out["embed_grad"]["speedup_rows"]
    out["equality_ok"] = all(v == "ok" for v in out["equality"].values())
    return out


def bench_vit(batch=64, warmup=3, iters=15, **cfg_overrides):
    """ViT-base/16 image-classification fine-tune step (the vision side of
    the flagship trunk; same 6ND + attention-inclusive MFU accounting as
    the LM cells, with T = n_patches + 1)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import vit as hvit

    kw = dict(n_classes=1000, dtype=jnp.bfloat16, remat=True)
    kw.update(cfg_overrides)
    cfg = hvit.ViTConfig(**kw)
    params = hvit.init_params(jax.random.PRNGKey(0), cfg)
    n_params = hvit.count_params(params)
    opt = hvit.init_opt_state(params)
    step = hvit.make_train_step(cfg, lr=1e-4)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, cfg.n_channels, cfg.image_size,
                              cfg.image_size), jnp.float32)
    y = jnp.asarray(rng.randint(0, cfg.n_classes, batch), jnp.int32)
    loss = None
    for _ in range(warmup):
        loss, _, params, opt = step(params, opt, x, y)
    float(np.asarray(loss))   # hard sync (see bench_bert)
    t0 = time.time()
    for _ in range(iters):
        loss, _, params, opt = step(params, opt, x, y)
    float(np.asarray(loss))
    dt = (time.time() - t0) / iters
    T = cfg.seq_len
    flops_6nd = 6.0 * n_params * batch * T
    flops_attn = _attn_flops(batch, T, cfg.n_layers, cfg.d_model,
                             causal=False)
    return {"images_per_sec": round(batch / dt, 1),
            "step_ms": round(dt * 1000, 2),
            "mfu_6nd": _mfu(flops_6nd, dt),
            "mfu_attn_incl": _mfu(flops_6nd + flops_attn, dt),
            "n_params": n_params}


def bench_pipeline_ab(d_model=512, n_layers=8, d_ff=2048, vocab_size=8192,
                      seq=256, mb=4, microbatches=16, pp=4):
    """GPipe vs 1F1B (both window endpoints) on a pp4/dp2 virtual mesh:
    per-stage bubble accounting (host schedule table) and AOT-compiled
    per-device memory for THREE cases — gpipe, 1f1b (default 2pp
    window), 1f1b_minmem (classic pp window: least stash, half-rate
    steady state). The 1F1B selling point is the stash: O(pp) instead
    of O(M). No wall-clock — a CPU mesh says nothing about ICI timing;
    memory and schedule structure are backend-independent. The cell's
    timeout budget covers the three AOT compiles (~30s total on the
    bench host's CPU)."""
    import jax
    from hetu_tpu.models import transformer as tfm
    from hetu_tpu.parallel import mesh as meshlib
    from hetu_tpu.parallel import pipeline as pplib
    from hetu_tpu.utils import ensure_devices

    ensure_devices(8)
    cfg = tfm.TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=d_model // 64,
        n_layers=n_layers, d_ff=d_ff, max_seq_len=seq,
        dtype=jax.numpy.float32, remat=False)
    mesh = meshlib.make_mesh(dp=8 // pp, pp=pp,
                             devices=jax.devices()[:8])
    M = microbatches
    p_sds = jax.eval_shape(
        lambda: pplib.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh))
    o_sds = jax.eval_shape(tfm.init_opt_state, p_sds)
    tok = jax.ShapeDtypeStruct((M, mb, seq), jax.numpy.int32)

    out = {"config": {"d_model": d_model, "n_layers": n_layers, "pp": pp,
                      "microbatches": M, "seq": seq, "mb": mb},
           "schedule": pplib.schedule_stats(pp, M),
           # the memory/duty tradeoff's other endpoint: classic 1F1B
           # window (stash <= pp, half-rate steady state)
           "schedule_minmem": pplib.schedule_stats(pp, M,
                                                   max_inflight=pp)["1f1b"]}
    cases = (("gpipe", pplib.make_pipeline_train_step, {}),
             ("1f1b", pplib.make_pipeline_train_step_1f1b, {}),
             ("1f1b_minmem", pplib.make_pipeline_train_step_1f1b,
              {"max_inflight": pp}))
    for label, make, kw in cases:
        step = make(cfg, mesh, num_microbatches=M, lr=1e-3, **kw)
        ma = step.lower(p_sds, o_sds, tok, tok).compile().memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        out[label] = {
            "per_device_mib": round(peak / 2**20, 1),
            "temp_mib": round(ma.temp_size_in_bytes / 2**20, 1),
        }
    out["temp_ratio_gpipe_over_1f1b"] = round(
        out["gpipe"]["temp_mib"] / max(out["1f1b"]["temp_mib"], 0.1), 2)
    return out


@contextlib.contextmanager
def _smoke_trace_dir(smoke):
    """Trace dir for smoke runs, DELETED on exit — smoke only exercises the
    capture path, and the former bare mkdtemp leaked a hetu_bench_* dir per
    run. Yields None outside smoke (or when the driver exported
    HETU_BENCH_TRACE: real runs get their per-section dir from
    _capture_trace and must keep it)."""
    if smoke and not os.environ.get("HETU_BENCH_TRACE"):
        with tempfile.TemporaryDirectory(prefix="hetu_bench_") as td:
            yield os.path.join(td, "trace")
    else:
        yield None


def _run_section(name):
    """Child mode: compute ONE section, print one JSON object, exit.
    Runs in its own process so a hung compile can be killed from outside
    — SIGALRM cannot interrupt a stuck C call.

    HETU_BENCH_SMOKE=1 shrinks every section to seconds-scale configs so
    the whole section surface can execute on the CPU backend in tests —
    the driver's one hardware run must never be the first time a
    section's Python path executes (tests/test_bench_sections.py)."""
    smoke = os.environ.get("HETU_BENCH_SMOKE") == "1"
    if smoke:
        # smoke mode is the CPU pin, made on purpose before jax loads
        os.environ["JAX_PLATFORMS"] = "cpu"
    # every section child shares one persistent compile cache (the
    # directory JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache)
    from hetu_tpu.utils import use_compile_cache
    use_compile_cache()
    # tiny-but-structurally-identical transformer dialect for smoke runs
    tiny = dict(vocab_size=512, d_model=64, n_heads=4, n_layers=2,
                d_ff=128, max_seq_len=64)
    out = {}
    if name.startswith("resnet:"):
        _, bs, tag = name.split(":")
        dtype = None if tag == "f32" else "bfloat16"
        kw = dict(batch_size=8, warmup=1, iters=2) if smoke else \
            dict(batch_size=int(bs))
        sps, ms, mfu = bench_resnet18(dtype=dtype, **kw)
        out = {"samples_per_sec": round(sps, 1), "step_ms": round(ms, 2),
               "mfu": mfu}
    elif name == "twin":
        _import_models("cnn")
        import jax_twin
        kw = dict(batch_size=8, warmup=1, iters=2) if smoke else \
            dict(batch_size=512)
        tsps, tms = jax_twin.bench(dtype="bf16", **kw)
        out = {"samples_per_sec": round(tsps, 1), "step_ms": round(tms, 2)}
    elif name == "transformer":
        out = (bench_transformer(batch=2, seq=64, warmup=1, iters=2, **tiny)
               if smoke else bench_transformer())
    elif name == "transformer350":
        # flagship-scale proof point (~350M params): MFU must rise with
        # model size if the 38M config is shape-bound, as claimed
        from hetu_tpu.models import transformer as tfm

        big = dict(vocab_size=32768, d_model=1024, n_heads=16,
                   n_layers=24, d_ff=4096, max_seq_len=512)
        cfg350 = tfm.TransformerConfig(remat=True,
                                       **(tiny if smoke else big))
        # smoke exercises the trace path like the bert cell does (env
        # runs get their per-section subdir from _capture_trace)
        with _smoke_trace_dir(smoke) as tdir350:
            out = bench_transformer(
                cfg=cfg350, batch=2 if smoke else 8,
                seq=64 if smoke else 512, warmup=1 if smoke else 2,
                iters=2 if smoke else 8, trace_dir=tdir350,
                trace_label="transformer350")
    elif name == "decode":
        kw = dict(batch=2, prompt_len=4, max_len=16) if smoke else {}
        dtoks, dms = bench_decode(**kw)
        out = {"tokens_per_sec": round(dtoks, 0),
               "ms_per_token": round(dms, 3)}
    elif name == "flash4k":
        kw = dict(b=1, h=2, s=256, d=64, iters=2) if smoke else {}
        out = bench_flash_attention(**kw)
    elif name == "bert":
        if smoke:
            # smoke exercises the trace-capture path too (the real cell
            # only traces when the driver exports HETU_BENCH_TRACE)
            with _smoke_trace_dir(smoke) as tdir:
                out = bench_bert(batch_size=2, seq_len=64, warmup=1,
                                 iters=2, trace_dir=tdir, **tiny)
        else:
            out = bench_bert()
    elif name == "vit":
        kw = (dict(batch=2, warmup=1, iters=2, image_size=32, patch_size=8,
                   d_model=64, n_heads=4, n_layers=2, d_ff=128,
                   n_classes=10) if smoke else {})
        out = bench_vit(**kw)
    elif name == "pipeline":
        # GPipe vs 1F1B (x2 windows) on an 8-device VIRTUAL CPU mesh (cell
        # measures the schedules' memory law and bubble accounting, which
        # need pp>1 — the bench host has one chip; _run_section pins the
        # child to the CPU backend for exactly this section)
        # smoke keeps microbatches > 2*pp so the minmem window actually
        # binds (at M <= pp both windows yield the same table/ring)
        out = bench_pipeline_ab(**(dict(d_model=64, n_layers=4, d_ff=128,
                                        vocab_size=512, seq=32, mb=2,
                                        microbatches=12) if smoke else {}))
    elif name == "introspect":
        # hetuscope overhead cell (docs/OBSERVABILITY.md): the <5%-at-
        # default-cadence claim is MEASURED here, not asserted
        kw = (dict(width=32, batch=16, iters=12, warmup=4)
              if smoke else {})
        out = bench_introspect_overhead(**kw)
    elif name == "watch":
        # hetuwatch overhead cell (docs/OBSERVABILITY.md pillar 6): the
        # <=2%-armed claim is MEASURED here, not asserted
        kw = (dict(width=32, batch=16, iters=12, warmup=4, windows=2)
              if smoke else {})
        out = bench_watch_overhead(**kw)
    elif name == "pilot":
        # hetupilot armed-idle cell (docs/FAULT_TOLERANCE.md): the
        # <1%-idle claim is MEASURED here, not asserted
        kw = (dict(width=32, batch=16, iters=10, warmup=3, windows=2)
              if smoke else {})
        out = bench_pilot_overhead(**kw)
        out["servers"] = 1
    elif name == "story":
        # hetustory run-identity stamping cell (docs/OBSERVABILITY.md
        # pillar 7): the <0.5%/step claim is MEASURED here, not asserted
        kw = (dict(iters=500, warmup=50, windows=2, step_iters=8,
                   step_warmup=2) if smoke else {})
        out = bench_story_overhead(**kw)
    elif name == "probe":
        import jax
        import jax.numpy as jnp
        # the run needs a TPU: any other backend fails the probe (rc != 0,
        # one line), and the parent ends the run before any section. Only
        # smoke mode, pinned to the CPU on purpose, may probe a CPU.
        devs = jax.devices()
        if not smoke and jax.default_backend() != "tpu":
            raise SystemExit(
                f"bench probe: jax backend is {jax.default_backend()!r} "
                f"({devs[0].device_kind}), not a TPU; only "
                "HETU_BENCH_SMOKE=1 runs off the chip")
        x = jnp.ones((512, 512))
        out = {"ok": float(jnp.sum(jax.jit(lambda a: a @ a)(x))) > 0,
               "devices": len(devs)}
    elif name == "wdl":
        kw = dict(batch_size=16, warmup=1, iters=4,
                  feature_dim=1000) if smoke else {}
        out = bench_wdl_ps(**kw)
        out["servers"] = 2
    elif name == "comm_quant_ps":
        kw = (dict(batch_size=32, steps=12, feature_dim=1000, n_test=128,
                   warmup=2, n_train=256) if smoke else {})
        out = bench_comm_quant_ps(**kw)
        out["servers"] = 2
    elif name == "comm_quant_dp":
        kw = (dict(width=64, batch=32, steps=8, warmup=2) if smoke else {})
        out = bench_comm_quant_dp(**kw)
    elif name == "trail":
        # hetutrail overhead cell (docs/OBSERVABILITY.md pillar 5): the
        # <2%-with-ring-enabled claim is MEASURED here, not asserted
        kw = (dict(batch_size=32, iters=6, rows=500, warmup=2, windows=2)
              if smoke else {})
        out = bench_trail_overhead(**kw)
        out["servers"] = 2
    elif name == "chaos":
        # hetuchaos hardening cell (docs/FAULT_TOLERANCE.md): the
        # retry/CRC <= 2%/step claim is MEASURED here, not asserted
        kw = (dict(batch_size=32, iters=6, rows=500, warmup=2, windows=2)
              if smoke else {})
        out = bench_chaos_hardening(**kw)
        out["servers"] = 2
    elif name == "snapshot":
        # hetusave coordinated-snapshot cell (docs/FAULT_TOLERANCE.md):
        # the <5%/step amortized stall claim is MEASURED here, not
        # asserted
        kw = (dict(batch_size=32, iters=10, rows=500, warmup=2,
                   windows=2, snap_every=5) if smoke else {})
        out = bench_snapshot_overhead(**kw)
        out["servers"] = 2
    elif name == "kernels":
        kw = (dict(vocab=5000, dim=32, batch=512, lookups=2, warmup=1,
                   iters=3) if smoke else {})
        out = bench_kernels(**kw)
    elif name == "planner":
        # hetuplan predicted-vs-measured cell (docs/ANALYSIS.md Tier C):
        # the 30%-of-measured acceptance for the calibrated prediction
        kw = (dict(width=64, target_width=128, batch=64, warmup=3,
                   iters=8) if smoke else {})
        out = bench_planner(**kw)
    else:
        raise SystemExit(f"unknown section {name}")
    import jax
    out["_device"] = str(jax.devices()[0].device_kind)
    print(json.dumps(out))


# sections pinned to the CPU on purpose, whatever the host's backend (their
# rows are stamped device_kind cpu): the pipeline A/B needs 8 devices
# (pp>1), which a 1-chip host cannot provide, and the rest are
# framework-relative host-side A/Bs. ROADMAP S0/S7 replaces them with
# on-chip cells.
_CPU = {"JAX_PLATFORMS": "cpu"}
_CPU8 = {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
SECTION_ENV = {
    "pipeline": _CPU8,
    # framework-overhead A/B: the delta measures hetuscope's host work
    "introspect": _CPU,
    # hetuq A/Bs (docs/COMM_QUANT.md): bytes-on-wire and AUC/loss deltas
    # are device-independent. The DP cell additionally needs an 8-device
    # mesh for a real dp axis.
    "comm_quant_ps": _CPU,
    "comm_quant_dp": _CPU8,
    # hetukern cell (docs/KERNELS.md): the dense-vs-rows embed-grad A/B is
    # a structural HBM claim and the equality smoke drives interpret-mode
    # Pallas; chip_smoke.py's kernels phase is the compiled check
    "kernels": _CPU,
    # hetutrail / hetuwatch / hetupilot / hetustory / hetuchaos / hetusave
    # overhead A/Bs and the hetuplan calibration round-trip: the cost
    # being measured is host-side (dict arithmetic, serialization, the
    # PS cluster's CRC, the quiesce barrier + shard write)
    "trail": _CPU, "watch": _CPU, "pilot": _CPU, "story": _CPU,
    "chaos": _CPU, "snapshot": _CPU, "planner": _CPU,
}


# pgid of the in-flight section child: the SIGTERM emergency emitter kills
# it so a driver-terminated bench leaves no orphaned PS cluster behind
_CURRENT_CHILD_PGID = [None]


def _section_subprocess(name, timeout):
    """Run one section in a child process group with a hard timeout. The
    whole GROUP is killed on timeout — the wdl section spawns a PS
    scheduler/server that must not outlive a killed child (and whose open
    pipes would otherwise stall communicate() after a child crash)."""
    import subprocess
    cmd = [sys.executable, os.path.abspath(__file__), "--run-section", name]
    env = os.environ.copy()
    env.update(SECTION_ENV.get(name, {}))
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            cwd=os.path.dirname(os.path.abspath(__file__)),
                            env=env, start_new_session=True)
    _CURRENT_CHILD_PGID[0] = proc.pid
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.communicate()
        # "hang" is the structured marker every triage path keys on — an
        # rc!=0 crash whose stderr merely CONTAINS "timed out" must not be
        # classified as a backend hang
        return {"error": f"timed out after {timeout}s (hung compile?)",
                "hang": True}
    finally:
        _CURRENT_CHILD_PGID[0] = None
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    if proc.returncode != 0:
        tail = (stderr or "").strip().splitlines()[-3:]
        return {"error": f"rc={proc.returncode}: " + " | ".join(tail)[:300]}
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue   # progress noise that merely looks like JSON
    return {"error": "no JSON line from section"}


def _git_sha():
    import subprocess
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True, stderr=subprocess.DEVNULL).strip()
    except Exception:  # noqa: BLE001 — not a git checkout / no git
        return None


class _Ledger:
    """Durable per-cell scoreboard (BENCH_PARTIAL.json, git-ignored).

    Every completed cell is written to disk the moment it finishes, so a
    run killed part-way loses nothing: the next invocation reuses the
    recorded cells and spends its hardware minutes only on the missing
    ones. The final JSON line merges ledger + fresh; entries recorded at
    a different git sha are re-measured, not served (HETU_BENCH_REUSE_STALE
    opts in, flagged), and outside a git checkout — where there is no sha
    to compare — nothing is ever served. Smoke runs never open a
    ledger at all (main() passes an empty path): smoke exists to validate
    the section pipeline, and serving cached cells would defeat that.
    Reference analogue: PS load recording persists to log_path
    (/root/reference/python/hetu/gpu_ops/executor.py:292-295); this is the
    same durability idea applied to the round scoreboard."""

    def __init__(self, path):
        self.path = path or None
        self.sha = _git_sha()
        self.cells = {}
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    data = json.load(f)
                self.cells = data["cells"] if isinstance(data, dict) else {}
            except (KeyError, ValueError, OSError) as e:
                print(f"# bench ledger unreadable ({e}); starting fresh",
                      file=sys.stderr)

    def reuse(self, key):
        """A reusable entry is a SUCCESS recorded at THIS git sha; errors
        and hangs are always re-attempted, and a cell from a different
        commit is re-measured rather than fed into the merged headline
        (HETU_BENCH_REUSE_STALE=1 opts back into serving it, flagged
        ``stale`` — for triage runs on a dead backend, where an old number
        beats none). Returns the result dict with an ``_ledger``
        provenance stamp, or None."""
        ent = self.cells.get(key)
        if self.sha is None or not isinstance(ent, dict):
            # no sha (a copy that is not a git checkout): None == None
            # would pass every recorded cell off as measured at this code
            return None
        result = ent.get("result")
        if not isinstance(result, dict) or "error" in result:
            return None
        out = dict(result)
        prov = {"ts": ent.get("ts")}
        if ent.get("sha") != self.sha:
            # resilience.env_truthy's convention, re-inlined because this
            # driver must stay jax-free (importing hetu_tpu pulls jax):
            # REUSE_STALE=false means what it says
            if os.environ.get("HETU_BENCH_REUSE_STALE",
                              "").strip().lower() not in ("1", "true",
                                                          "yes", "on"):
                return None
            prov["stale"] = f"recorded at {ent.get('sha')}, HEAD is {self.sha}"
        out["_ledger"] = prov
        return out

    def record(self, key, result, device=None):
        self.cells[key] = {
            "result": result, "sha": self.sha,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        if not self.path:
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"cells": self.cells}, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)   # atomic: a kill never corrupts it
        self._telemetry_line(key, result, device)

    def _telemetry_line(self, key, result, device):
        """One JSONL line per completed cell, appended next to the ledger
        (BENCH_TELEMETRY.jsonl): records the cell's headline numbers PLUS
        device_kind and the peak profiler.DEVICE_PEAKS holds for it (None
        for an unknown kind) — an MFU without the peak it was computed
        against is not a measurement."""
        path = os.path.join(os.path.dirname(os.path.abspath(self.path)),
                            "BENCH_TELEMETRY.jsonl")
        peaks = _profiler().device_peaks(device)
        rec = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), "cell": key,
               "sha": self.sha, "device_kind": device,
               "peak_tflops": peaks["tflops"] if peaks else None}
        if isinstance(result, dict):
            for k in ("samples_per_sec", "step_ms", "mfu", "mfu_6nd",
                      "mfu_attn_incl", "tokens_per_sec",
                      "introspect_overhead_pct", "trail_overhead_pct",
                      "watch_overhead_pct", "watch_observe_ms",
                      "watch_amortized_pct", "observations",
                      "pilot_overhead_pct", "pilot_boundary_ms",
                      "pilot_amortized_pct",
                      "story_overhead_pct", "record_us_off",
                      "record_us_on",
                      "client_spans", "step_ms_off",
                      "step_ms_on", "bytes_wire_ratio", "auc_off",
                      "auc_int8", "auc_delta", "final_loss_off",
                      "loss_delta_int8", "loss_delta_fp8",
                      "dense_step_ms", "rows_step_ms", "speedup_rows",
                      "equality_ok", "measured_step_ms",
                      "predicted_step_ms", "plan_err_pct",
                      "plan_comm_mode", "crc_overhead_pct", "crc_rejects",
                      "snapshot_stall_pct", "snapshot_wall_ms"):
                if result.get(k) is not None:
                    rec[k] = result[k]
        try:
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError as e:
            print(f"# bench telemetry line skipped ({e})", file=sys.stderr)


def _wait_for_backend(budget, detail):
    """Probe-wait loop for a hung backend the caller JUST observed (so it
    sleeps before the first probe instead of re-confirming the hang).
    Spends up to ``budget[0]`` seconds (a single-element list so the spend
    is SHARED across every hang in the run) probing every 240s. Returns
    True when a probe succeeds; False when the budget is gone or a probe
    ran and failed (no TPU: waiting cannot help)."""
    while True:
        if budget[0] < 240 + 180:
            return False
        print(f"# backend down; retrying probe in 240s "
              f"({int(budget[0])}s shared wait budget left)",
              file=sys.stderr, flush=True)
        time.sleep(240)
        budget[0] -= 240
        t0 = time.time()
        out = _section_subprocess("probe", 180)
        budget[0] -= time.time() - t0
        if "error" not in out:
            detail["outage_recoveries"] = detail.get("outage_recoveries", 0) + 1
            if out.get("_device"):
                detail.setdefault("device", out["_device"])
            return True
        if not out.get("hang"):
            # the probe ran and FAILED: this machine has no usable TPU
            detail["_probe"] = out
            return False


def _assemble_final(detail, section_keys, error=None):
    """The ONE final JSON line, from whatever cells exist so far.

    Factored out of main() so the SIGTERM emergency path emits the same
    structure: completed cells keep their numbers, the headline comes from
    whichever resnet cells finished, and ``incomplete_cells`` names every
    section that has no measurement — so a cut-short run yields a partial
    trajectory point that SAYS it is partial (a driver cap that kills the
    run must still leave a JSON line) instead of reading as a win, a loss,
    or nothing."""
    headline = 0.0
    for k, v in detail.items():
        if k.startswith("resnet18_") and isinstance(v, dict):
            headline = max(headline, v.get("samples_per_sec") or 0.0)
    incomplete = [k for k in section_keys
                  if not isinstance(detail.get(k), dict)
                  or "error" in detail[k]]
    line = {
        "metric": "resnet18_cifar10_train_samples_per_sec_per_chip",
        "value": round(headline, 1) if headline else None,
        "unit": "samples/sec/chip",
        "vs_baseline": (round(headline / BASELINE_SAMPLES_PER_SEC, 3)
                        if headline and BASELINE_SAMPLES_PER_SEC else None),
        "detail": detail,
    }
    if error:
        line["error"] = error
    if incomplete:
        line["incomplete_cells"] = incomplete
    return line


def _install_emergency_emit(detail, section_keys):
    """SIGTERM handler (installed BEFORE the first timed window): the
    driver kills a over-budget bench with ``timeout -k 10``, which sends
    SIGTERM then SIGKILL 10 s later — enough room to print the final line
    with every completed cell, kill the in-flight section child's process
    group, and exit 75 (EX_TEMPFAIL, the repo's preemption convention)."""
    def _emergency(signum, frame):
        line = _assemble_final(
            detail, section_keys,
            error=f"terminated by signal {signum} before completion")
        print(json.dumps(line), flush=True)
        pgid = _CURRENT_CHILD_PGID[0]
        if pgid:
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        os._exit(75)
    signal.signal(signal.SIGTERM, _emergency)


def _latest_good_round(here):
    """Newest BENCH round artifact with at least one gateable measurement
    (BENCH_rNN.json driver wrappers and BENCH_SELF_rNN_partial.json
    ledgers both qualify) — the default --gate baseline. A parsed-null
    wrapper (a round that died rc=124) is exactly what this must skip."""
    prof = _profiler()
    candidates = []
    for path in glob.glob(os.path.join(here, "BENCH_*r[0-9]*.json")):
        m = re.search(r"r(\d+)", os.path.basename(path))
        if m:
            candidates.append((int(m.group(1)), path))
    for _, path in sorted(candidates, reverse=True):
        try:
            cells, _meta = prof.load_summary(path)
        except (OSError, ValueError):
            continue
        if prof.summary_has_measurement(cells):
            return path
    return None


def main():
    # the parent NEVER touches jax: a hung backend must not stall the
    # driver's one-JSON-line contract, and a parent that held the chip
    # would leave none for its section children
    detail = {}
    backend_dead = False
    # durable scoreboard: HETU_BENCH_LEDGER overrides the path; empty
    # string disables (the scripted driver tests run ledger-less). Smoke
    # mode NEVER opens a ledger — a smoke run must execute every section
    # (that's what it validates), and its toy numbers must never be
    # served to (or shadow) a real run.
    lpath = os.environ.get("HETU_BENCH_LEDGER")
    if lpath is None:
        lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_PARTIAL.json")
    if os.environ.get("HETU_BENCH_SMOKE") == "1":
        lpath = ""
    ledger = _Ledger(lpath)
    alive_hangs = 0   # consecutive section hangs while probes still answer
    # one shared wait budget for every backend hang in the run (at-start
    # AND mid-run), so repeated hangs can't stretch the bench unboundedly
    wait_budget = [float(os.environ.get("HETU_BENCH_PROBE_WAIT_S", "2700"))]

    # cheap canary first: a machine without a usable TPU is detected by one
    # 180s probe, before any section timeout is spent.
    # ordered by value-per-minute: the headline candidates first, then the
    # BERT MFU story, then the rest — a late backend loss with an exhausted
    # wait budget costs the least-important cells.
    sections = [("_probe", "probe", 180),
                ("resnet18_bf16_bs128", "resnet:128:bf16", 420),
                ("resnet18_f32_bs128", "resnet:128:f32", 420),
                ("resnet18_f32_bs256", "resnet:256:f32", 420),
                ("resnet18_bf16_bs256", "resnet:256:bf16", 420),
                ("resnet18_bf16_bs512", "resnet:512:bf16", 420)]
    if "--fast" not in sys.argv:
        sections += [("bert_base_pretrain_seq512", "bert", 600),
                     ("transformer_38M_seq512", "transformer", 420),
                     ("transformer_350M_seq512", "transformer350", 600),
                     ("jax_native_twin_bf16_bs512", "twin", 420),
                     ("decode_38M_greedy", "decode", 420),
                     ("flash_attention_seq4096", "flash4k", 420),
                     ("vit_base_finetune", "vit", 600),
                     ("pipeline_gpipe_vs_1f1b", "pipeline", 600),
                     ("wdl_criteo_hybrid_ps", "wdl", 600),
                     ("comm_quant_ps_wdl", "comm_quant_ps", 600),
                     ("comm_quant_dp_mlp", "comm_quant_dp", 600),
                     ("introspect_overhead", "introspect", 420),
                     ("trail_overhead", "trail", 600),
                     ("watch_overhead", "watch", 420),
                     ("pilot_overhead", "pilot", 420),
                     ("story_overhead", "story", 420),
                     ("chaos_overhead", "chaos", 600),
                     ("snapshot_overhead", "snapshot", 600),
                     ("kernels_tier", "kernels", 600),
                     ("planner_residual", "planner", 420)]
    # emergency emitter BEFORE the first timed window: a driver kill from
    # here on still produces the final line with every completed cell
    section_keys = [k for k, n, _t in sections if n != "probe"]
    _install_emergency_emit(detail, section_keys)

    # Global wall-clock budget (HETU_BENCH_DEADLINE_S, 0 = off): the
    # driver wraps the whole bench in `timeout -k`, and a run whose
    # section timeouts SUM past that cap is killed rc=124 with no
    # trajectory point — a hole the emergency line only partially fixes
    # (SIGTERM still loses the in-flight cell and any stdout race loses
    # the line entirely). With a deadline set, each
    # cell's timeout is clamped to the time actually remaining and a
    # cell that no longer fits is SKIPPED with a named reason — the
    # bench always finishes inside the cap and emits its own final line.
    deadline_s = float(os.environ.get("HETU_BENCH_DEADLINE_S", "0") or 0)
    bench_t0 = time.monotonic()
    # leave room after the last cell for the gate + final-line emit
    _DEADLINE_MARGIN_S, _MIN_CELL_S = 30.0, 60.0

    for key, name, timeout in sections:
        if deadline_s > 0:
            remaining = deadline_s - (time.monotonic() - bench_t0) \
                - _DEADLINE_MARGIN_S
            if remaining < _MIN_CELL_S:
                if name != "probe":
                    detail[key] = {"error": "skipped: global deadline "
                                   f"(HETU_BENCH_DEADLINE_S={deadline_s:g})"
                                   " exhausted"}
                continue
            timeout = min(timeout, int(remaining))
            # the outage wait budget must also fit inside the deadline: a
            # _wait_for_backend sleep past the cap turns a named-skip
            # round into a driver rc=124 kill with no final line.
            # HETU_BENCH_PROBE_WAIT_S semantics unchanged when no deadline
            # is set.
            wait_budget[0] = min(wait_budget[0], remaining)
        if name == "probe":
            # A probe that HANGS gets the at-start wait-and-retry within
            # the shared budget (HETU_BENCH_PROBE_WAIT_S, default 45 min).
            # A probe that ran and FAILED (rc != 0: no TPU here, or the
            # backend cannot multiply) ends the run non-zero now, before
            # any section can run on whatever backend was found.
            out = _section_subprocess(name, timeout)
            if "error" not in out:
                dev = out.pop("_device", None)
                if dev:
                    detail["device"] = dev
            elif out.get("hang"):
                wait_budget[0] -= timeout   # the observed hang IS attempt 1
                if not _wait_for_backend(wait_budget, detail):
                    backend_dead = True
                    detail.setdefault("_probe", out)
                # on recovery: nothing stale recorded — outage_recoveries
                # carries the "started down, came back" signal
            else:
                detail["_probe"] = out
            if "_probe" in detail and not detail["_probe"].get("hang"):
                print(f"# bench: probe failed: {detail['_probe']['error']}",
                      file=sys.stderr)
                print(json.dumps(_assemble_final(
                    detail, section_keys,
                    error="probe failed: no usable TPU backend")))
                sys.exit(1)
            continue
        cached = ledger.reuse(key)
        if cached is not None:
            # ledger reuse comes BEFORE the dead-backend/backstop skips:
            # a cell captured by an earlier invocation must survive a run
            # whose own hardware window is gone
            detail[key] = cached
            detail.setdefault("from_ledger", []).append(key)
            continue
        if backend_dead:
            # wait budget exhausted with the backend still hung: a NAMED
            # per-cell skip (machine-readable "skip" key) instead of
            # burning each cell's timeout into an rc=124 no-data round
            detail[key] = {"error": "skipped: backend unresponsive",
                           "skip": "backend_dead"}
            continue
        if alive_hangs >= 2:
            # backstop: probes answer but sections keep hanging (a systemic
            # compile-path hang, not an outage) — don't burn timeout+probe
            # on every remaining section
            detail[key] = {"error": "skipped: sections hanging with live "
                                    "backend"}
            continue
        out = _section_subprocess(name, timeout)
        # hang_kind: None = section completed (possibly rc!=0);
        # "alive" = hung while probes answer; "outage" = the backend's
        hang_kind = None
        if out.get("hang"):
            # a hung section is EITHER a hung backend or a genuinely hung
            # compile — a 180s probe tells them apart. Backend alive →
            # record the section failure and move on; backend hung → wait
            # it out and retry this section ONCE.
            t0 = time.time()
            probe = _section_subprocess("probe", 180)
            wait_budget[0] -= time.time() - t0
            if probe.get("hang"):
                # outage: the section's burned timeout counts against the
                # shared budget — repeated backend hangs must not stretch
                # the run unboundedly via un-charged section hangs
                wait_budget[0] -= timeout
                detail.setdefault("mid_run_outages", []).append(key)
                if _wait_for_backend(wait_budget, detail):
                    out = _section_subprocess(name, timeout)
                    if out.get("hang"):
                        # retry hung too — triage AGAIN before blaming the
                        # section: a flapping backend is not an alive-hang
                        t0 = time.time()
                        p2 = _section_subprocess("probe", 180)
                        wait_budget[0] -= time.time() - t0
                        if p2.get("hang"):
                            hang_kind = "outage"
                            out = {"error": "hung across outage retry "
                                            "(backend flapping)"}
                        else:
                            hang_kind = "alive"
                else:
                    backend_dead = True
                    detail[key] = {"error": "backend lost mid-run; wait "
                                            "budget exhausted",
                                   "skip": "backend_dead"}
                    continue
            else:
                hang_kind = "alive"
        # consecutive-hang bookkeeping: alive-hangs count toward the
        # backstop, completed sections reset, outage-attributed hangs
        # leave the counter untouched
        if hang_kind == "alive":
            alive_hangs += 1
        elif hang_kind is None:
            alive_hangs = 0
        if "error" not in out:
            dev = out.pop("_device", None)
            if dev and "device" not in detail:
                detail["device"] = dev
            ledger.record(key, out, device=dev)
        detail[key] = out

    # final line over the MERGED detail (fresh + ledger): a resnet cell
    # captured by a killed earlier invocation still counts; a value of None
    # is unmistakably a failure, not a catastrophic-regression-shaped
    # measurement, and incomplete_cells names what was not measured
    line = _assemble_final(detail, section_keys)

    if "--gate" in sys.argv:
        # self-report regression vs the last good trajectory round: the
        # verdict rides INSIDE the line (detailed in docs/PROFILING.md);
        # the driver's exit-code contract is untouched
        here = os.path.dirname(os.path.abspath(__file__))
        idx = sys.argv.index("--gate")
        baseline = (sys.argv[idx + 1]
                    if idx + 1 < len(sys.argv)
                    and not sys.argv[idx + 1].startswith("-") else None)
        baseline = baseline or _latest_good_round(here)
        if baseline is None:
            line["gate"] = {"error": "no usable baseline round found"}
        else:
            res = _profiler().gate_files(baseline, current_data=line)
            line["gate"] = {"baseline": os.path.basename(baseline),
                            "verdict": res.verdict, "status": res.status,
                            "regressions": res.regressions,
                            "incomplete": res.incomplete}
            print(f"# gate vs {baseline}: {res.verdict}", file=sys.stderr)

    print(json.dumps(line))
    if line["value"] is None:
        sys.exit(1)


if __name__ == "__main__":
    if "--run-section" in sys.argv:
        _run_section(sys.argv[sys.argv.index("--run-section") + 1])
    else:
        main()
