"""One run of one cell: set-up, a measured window, the per-layer readings
of a traced run, and the comparison with the plain reference that
decides ``correct``.

``run_cell`` is the whole run; ``bench/run.py`` is its command line.  A
test can call it with ``require_tpu=False`` and a smaller configuration
to drive everything but the look for a chip.
"""

from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench import checks, spec, stats, traffic
from bench.spec import ROOT, SpecError


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's own
    monitoring events."""

    compiles: int = 0
    compile_s: float = 0.0
    cache_loads: int = 0

    def install(self) -> CompileCounter:
        import jax

        def on_duration(event: str, duration: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_loads


@dataclass
class Ctx:
    """What the per-layer readers read once the window has closed."""

    cell: spec.Cell
    requests: list = field(default_factory=list)  # per-request dicts, window only
    kernel_calls: list = field(default_factory=list)  # the kind's kernel call records
    trace: object = None  # bench.trace.Summary
    peaks: dict | None = None


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_device(chips: int) -> dict:
    """The device JAX runs on; raises ``NoChip`` unless it is a TPU with
    at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform {d.platform!r} "
                     f"({d.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _forget_results(svc) -> None:
    """Drop every result the service has stored, so that no later request
    is served from its cache."""
    from repro.runtime.cache import ResultCache

    svc.cache = ResultCache(svc.cache.capacity)


def _warm_up(dep, mix: dict, plan: list) -> int:
    """Every round of every client, in the window's order, each position
    of a round drained before the next; raises if a request fails.
    Returns how many ran."""
    svc, n = dep.service, 0
    for r in range(int(mix["rounds"])):
        _forget_results(svc)
        for j in range(traffic.per_round(mix)):
            ids = [svc.submit(c[r][j].tenant, c[r][j].app, dep.dataset, c[r][j].params)
                   for c in plan]
            svc.drain(max_requests=mix["max_requests"])
            for rid in ids:
                if svc.poll(rid) != "done":
                    req = svc.request(rid)
                    raise RuntimeError(f"warm-up request {req.app} {req.params} failed: "
                                       f"{req.error}")
            n += len(ids)
    return n


def _window(dep, mix: dict, plan: list, seconds: float):
    """The measured window: a closed loop of ``mix['clients']`` clients,
    each replaying its rounds.  A pass through a client's rounds that
    starts inside the window runs whole, so each app's jobs count alike.
    Returns (records, open time); a record is (request id or None
    when refused, stats.Done, traffic.Request)."""
    from repro.workflow.requests import QueueFullError

    svc = dep.service
    per = traffic.per_round(mix)
    seq = [[req for rnd in c for req in rnd] for c in plan]
    sent = [0] * len(plan)
    owner: dict[int, tuple] = {}
    records: list = []

    def send(c: int) -> None:
        i = sent[c]
        sent[c] += 1
        if i % per == 0:
            _forget_results(svc)
        req = seq[c][i % len(seq[c])]
        t = time.perf_counter()
        try:
            with span("bench.submit"):
                rid = svc.submit(req.tenant, req.app, dep.dataset, req.params)
        except (QueueFullError, ValueError) as e:
            log(f"refused: {req.app} {req.params}: {e}")
            records.append((None, stats.Done(req.app, t, None, False), req))
            return
        owner[rid] = (c, stats.Done(req.app, t, None, False), req)

    t_open = time.perf_counter()
    t_close = t_open + seconds
    for c in range(len(plan)):
        send(c)
    while owner:
        with span("bench.step"):
            finished = svc.step(mix["max_requests"])
        t = time.perf_counter()
        for rid in finished:
            c, rec, req = owner.pop(rid)
            rec.done_t = t
            rec.ok = svc.poll(rid) == "done"
            records.append((rid, rec, req))
            if rec.ok and (t < t_close or sent[c] % len(seq[c])):
                send(c)
    return records, t_open


def _request_rows(svc, records) -> list[dict]:
    rows = []
    for rid, rec, req in records:
        row = {"app": req.app, "params": req.params, "latency_s": rec.latency_s, "ok": rec.ok}
        if rid is not None:
            row["compiles"] = int(svc.request(rid).compiles)
        if rec.ok:
            comm = getattr(svc.result(rid), "comm", None)
            if comm is not None:
                row["count_calls"] = int(comm.count_calls)
        rows.append(row)
    return rows


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


# the arithmetic of each end-to-end quantity; a metric named
# ``<quantity>`` or ``<quantity>.<cells>`` uses its quantity's
ARITHMETIC = {
    "setup_s": lambda done, setup_s: setup_s,
    "job_s": lambda done, setup_s: stats.job_s(done),
}


def end_to_end(cell: spec.Cell, done: list, setup_s: float) -> dict:
    """The cell's end-to-end metrics, by name."""
    out = {}
    for m in cell.end_to_end:
        quantity = m["name"].split(".", 1)[0]
        if quantity not in ARITHMETIC:
            raise SpecError(f"end-to-end metric {m['name']!r} has no arithmetic in "
                            "bench/harness.py")
        out[m["name"]] = {"value": ARITHMETIC[quantity](done, setup_s), "unit": m["unit"]}
    return out


def per_layer(cell: spec.Cell, ctx: Ctx, root: Path = ROOT) -> dict:
    """Each per-layer metric that its reader finds something to read for."""
    out = {}
    for m in cell.per_layer:
        got = spec.metric_reader(m["name"], root)(ctx)
        if got is None:
            log(f"per-layer {m['name']}: nothing to read")
            continue
        extra = got if isinstance(got, dict) else {"value": got}
        out[m["name"]] = {"value": extra.pop("value"), "unit": m["unit"], **extra}
    return out


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    root: Path = ROOT,
    config: dict | None = None,
    traffic_mix: dict | None = None,
    control: str | None = None,
    warm: bool = True,
) -> dict:
    """Run one cell once and return the result object (without printing).

    ``config`` and ``traffic_mix`` replace the cell's configuration and
    mix (tests run them small);
    ``control`` puts a broken variant in the timed path's place (see
    ``bench.checks``), for the runs and tests that must see ``correct``
    false; ``warm=False`` skips the warm-up, for runs that read only the
    comparison.  ``root`` is the checkout whose benchmark files (cell,
    configuration, mix, generator, kind, readers) describe the run."""
    cell = spec.load_cell(name, root)
    if config is not None:
        cell = spec.Cell(**{**cell.__dict__, "config": config})
    if traffic_mix is not None:
        cell = spec.Cell(**{**cell.__dict__, "traffic": traffic_mix})
    mix = cell.traffic
    import jax

    device = check_device(cell.chips) if require_tpu else {
        "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}
    peaks = spec.peaks(device["kind"], root) if require_tpu else None
    from repro.launch.mesh import enable_compile_cache

    cache_dir = enable_compile_cache()
    # keep every program, however quick to compile, so that a checkout's
    # later runs load all of them in set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter().install()
    from bench import deploy

    plan = traffic.rounds(mix, seed)
    with span("bench.setup.data"):
        dep = deploy.build(cell.config, seed, root)
    kind = dep.kind
    with checks.planted(control, kind):
        with span("bench.setup.warmup"):
            n_warm = _warm_up(dep, mix, plan) if warm else 0
        setup_s = time.perf_counter() - t_start
        c0 = counter.snapshot()
        log(f"set-up {setup_s:.3f} s: {n_warm} warm-up requests, {c0[0]} compiles "
            f"({c0[1]:.3f} s), {c0[2]} persistent-cache loads, cache dir {cache_dir}")
        trace_dir = root / ".bench_trace" / name
        recorder = None
        if trace:
            from bench.kernels import KernelRecorder
            from bench.trace import start

            shutil.rmtree(trace_dir, ignore_errors=True)
            recorder = KernelRecorder(kind.KERNELS).__enter__()
            start(trace_dir)
        with span("bench.window"):
            records, t_open = _window(dep, mix, plan, seconds)
        t_end = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            recorder.__exit__(None, None, None)
    c1 = counter.snapshot()
    log(f"window {t_end - t_open:.3f} s ({seconds} s open): {len(records)} requests; "
        f"compiles inside it {c1[0] - c0[0]} ({c1[1] - c0[1]:.3f} s), "
        f"persistent-cache loads {c1[2] - c0[2]}")
    memory_peak = _memory_peak()
    svc = dep.service
    rows = _request_rows(svc, records)
    done = [rec for _, rec, _ in records]
    device = {**device, "memory_peak_bytes": memory_peak}
    result: dict = {"correct": False, "attempted": len(done),
                    "failed": sum(1 for d in done if not d.ok)}
    if trace:
        from bench.trace import reduce

        summary = reduce(trace_dir)
        ctx = Ctx(cell=cell, requests=rows, kernel_calls=recorder.resolve(), trace=summary,
                  peaks=peaks)
        result["metrics"] = per_layer(cell, ctx, root)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = end_to_end(cell, done, setup_s)
    result["device"] = device
    # the reference runs once the window has closed, memory has been read
    # and the program's state is freed
    answers = checks.answers(dep, records)
    data = dep.rows
    del svc, dep, records
    gc.collect()
    compared = checks.compare(kind, data, answers, failed=result["failed"])
    if control in kind.CONTROLS:  # the program's readings first, then the control's
        result["program_checks"] = {c.name: {"value": c.value, "limit": c.limit}
                                    for c in compared}
        compared = checks.compare(kind, data, answers, failed=result["failed"],
                                  control=control)
    for c in compared:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r})")
    result["correct"] = all(c.ok for c in compared)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in compared}
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed % (1 << 63), args.seconds, bool(args.trace),
                          t_start=t_start)
    except (NoChip, SpecError) as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(finite(result)), flush=True)
    return 0


def finite(v):
    """``v`` with every float that JSON cannot hold (inf, nan) as a string,
    and NumPy scalars as Python numbers."""
    if isinstance(v, dict):
        return {k: finite(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [finite(x) for x in v]
    if hasattr(v, "item") and not isinstance(v, (str, bytes)):
        v = v.item()
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return v
