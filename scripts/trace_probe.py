"""Serve one benchmark cell on the chip with the program's own spans on
and two short profiles, and print what the benchmark's result line does
not hold: the device seconds under each named scope, the longest idle
gaps of the chip named by the span with the most self time in them, and
the host-event count of a profile with the host tracer on.

Usage, from the root of a checkout, on a machine with a TPU:

    python3 scripts/trace_probe.py --workload glasses1m.walk.backfill \
        --seed 7 --seconds 25

The cell is served as ``bench/run.py`` serves it (``bench.harness.run``,
untraced), with a ``FlightRecorder`` attached.  Part-way through the
window it takes profile A (device operations only, as the benchmark's
traced slice does) and then profile B (host tracer at level 1, Python
tracer off).  The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PROFILE_A_S = 6.0
PROFILE_B_S = 3.0
SCOPES = ("bypass", "depth", "saliency", "tsrc")
WAITS = ("queue.wait",)  # a chunk waiting for its tick is not host work


class Profiles:
    """Takes profile A, then profile B, once the server has ticked a few
    times past ``after_ticks``."""

    def __init__(self, rec, logdir: str, after_ticks: int, delay_s: float):
        self.rec, self.logdir = rec, logdir
        self.after_ticks, self.delay_s = after_ticks, delay_s
        self.marks = {}
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _profile(self, name: str, host_level: int, seconds: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = host_level
        t0 = time.monotonic()
        jax.profiler.start_trace(os.path.join(self.logdir, name), profiler_options=opts)
        time.sleep(seconds)
        jax.profiler.stop_trace()
        self.marks[name] = (t0, time.monotonic())

    def _run(self) -> None:
        try:
            while self.rec.n_ticks_recorded < self.after_ticks:
                time.sleep(0.01)
            time.sleep(self.delay_s)
            self.marks["before"] = time.monotonic()
            self._profile("a", 0, PROFILE_A_S)
            time.sleep(1.0)
            self._profile("b", 1, PROFILE_B_S)
        except BaseException as e:  # reported by the main thread
            self.error = e


def with_metadata_stats(tr, logdir: str):
    """``tr`` with each device operation's event-metadata stats merged
    into its stats.  A TPU profile keeps an operation's scope path in
    the stats of its event metadata, which ``jax.profiler.ProfileData``
    does not expose; the raw file is read with the XPlane protobuf
    module that the installed TensorFlow ships (loaded by path, without
    importing TensorFlow).  Without that module ``tr`` comes back as it
    was."""
    import importlib.util

    from bench import tracing

    tf = importlib.util.find_spec("tensorflow")
    pb2 = tf and os.path.join(
        tf.submodule_search_locations[0], "tsl", "profiler", "protobuf", "xplane_pb2.py"
    )
    if not pb2 or not os.path.exists(pb2):
        return tr
    spec = importlib.util.spec_from_file_location("xplane_pb2", pb2)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    space = mod.XSpace()
    with open(tracing.find_xplane(logdir), "rb") as f:
        space.ParseFromString(f.read())
    device = dict(tr.device)
    for plane in space.planes:
        if plane.name not in device:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        text = {
            k: {names.get(s.metadata_id): s.str_value for s in md.stats if s.str_value}
            for k, md in plane.event_metadata.items()
        }
        ops = [ln for ln in plane.lines if ln.name == tracing.OPS_LINE]
        raw = [e.metadata_id for ln in ops for e in ln.events]
        if len(raw) == len(device[plane.name]):
            device[plane.name] = [
                e._replace(stats={**e.stats, **text[i]})
                for e, i in zip(device[plane.name], raw)
            ]
    return tr._replace(device=device)


def frames_per_s(ticks, cf: int, t0: float, t1: float):
    """Frames read back by the ticks that closed in ``[t0, t1]``, per
    second of that interval."""
    done = [tk for tk in ticks if t0 <= tk["t1"] <= t1]
    if t1 <= t0 or not done:
        return None
    return sum(len(tk["chunks"]) for tk in done) * cf / (t1 - t0)


def gap_table(tr, rec, n: int = 10):
    """The ``n`` longest idle gaps of the first chip, each named by the
    span name with the most self time in it (chunks waiting in their
    queue aside), with each name's share and the share no span covers."""
    from bench import stats, tracing
    from repro.obs.trace import self_seconds

    plane = sorted(tr.device)[0]
    gaps, edge = [], 0.0
    for a, b in tracing.busy_intervals(tr, plane) + [(tr.window_s, tr.window_s)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    rows, total = [], collections.Counter()
    for g0, g1 in gaps[:n]:
        spans = [s for s in rec.spans_on_profile(tr.start_ns, g0, g1) if s[0] not in WAITS]
        own = self_seconds(spans, g0, g1)
        covered = sum(b - a for a, b in stats.interval_union([(s[1], s[2]) for s in spans], g0, g1))
        total.update(own)
        total["(no span)"] += (g1 - g0) - covered
        rows.append({
            "seconds": g1 - g0,
            "name": max(own, key=own.get) if own else "(no span)",
            "self_s": {k: round(v, 6) for k, v in sorted(own.items(), key=lambda kv: -kv[1])},
            "no_span_s": (g1 - g0) - covered,
        })
    gap_s = sum(r["seconds"] for r in rows)
    shares = {k: 100.0 * v / gap_s for k, v in total.most_common()} if gap_s else {}
    return rows, gap_s, shares


def probe(cfg, mix, *, seed: int, seconds: float, logdir: str) -> dict:
    from bench import harness, kernels, scopes, spans, tracing
    from repro.obs.trace import FlightRecorder

    rec = FlightRecorder(capacity=1 << 16)
    prof = Profiles(rec, logdir, mix["warmup_ticks"] + 5, min(3.0, seconds / 5))

    served = []

    def attach(srv):
        srv.recorder = rec
        served.append(srv)
        prof.thread.start()

    e2e = [{"name": "frames_per_s", "unit": "frames/s"}, {"name": "setup_s", "unit": "s"}]
    out = harness.run(
        cfg, mix, seed=seed, seconds=seconds, trace=False, e2e=e2e, per_layer=[],
        t_start=time.monotonic(), logdir=os.path.join(logdir, "unused"),
        before_window=attach,
    )
    prof.thread.join()
    if prof.error is not None:
        raise prof.error
    cf = cfg["chunk_frames"]
    ticks = rec.ticks()
    first = ticks[mix["warmup_ticks"]]["t0"]
    before = [tk for tk in ticks if first <= tk["t0"] and tk["t1"] <= prof.marks["before"]]
    stepped = [tk for tk in before if tk["chunks"]]
    line = {
        "correct": out.correct, "checks": out.checks, "metrics": out.metrics,
        "device": out.device,
        # Whole run, warm-up and drain included.
        "wire_counters": {
            name: served[0].metrics.value(name)
            for name in ("wire_frames_in_total", "wire_frames_during_step_total")
        },
        "untraced": {
            "frames_per_s": frames_per_s(ticks, cf, first, prof.marks["before"]),
            "ticks_per_s": len(stepped) / (prof.marks["before"] - first),
            "chunks_per_tick": sum(len(tk["chunks"]) for tk in stepped) / max(1, len(stepped)),
            "wire.lock_wait_ms": spans.chunk_ms(before, "wire.lock_wait"),
            "wire.decode_span_ms": spans.chunk_ms(before, "wire.decode"),
            "queue.wait_ms": spans.chunk_ms(before, "queue.wait"),
            "tick.lock_wait_ms": spans.tick_ms(before, "lock_wait"),
            "tick.stack_ms": spans.tick_ms(before, "stack"),
            "tick.dispatch_ms": spans.tick_ms(before, "dispatch"),
            "tick.readback_ms": spans.tick_ms(before, "readback"),
        },
    }
    for name in ("a", "b"):
        tr = tracing.load(os.path.join(logdir, name))
        if name == "a":
            plain_tsrc = scopes.seconds_under(tr, "tsrc")
            tr = with_metadata_stats(tr, os.path.join(logdir, name))
        m0, m1 = prof.marks[name]
        section = {
            "window_s": tr.window_s,
            "frames_per_s": frames_per_s(ticks, cf, m0, m1),
            "device_ops": sum(len(v) for v in tr.device.values()),
            "host_events": len(tr.host),
            "host_top": collections.Counter(e.name for e in tr.host).most_common(12),
        }
        if name == "a" and tr.device:
            frames = sum(
                len(tk["chunks"]) for tk in ticks if m0 <= tk["t1"] <= m1
            ) * cf
            busy = tracing.busy_seconds(tr)
            section["idle_pct"] = 100.0 * (1.0 - busy / tr.window_s)
            section["device_us_per_frame"] = 1e6 * busy / frames
            section["kernel_us_per_frame"] = 1e6 * tracing.kernel_seconds(tr, kernels.REPROJECT_MATCH) / frames
            section["tsrc_us_per_frame"] = 1e6 * scopes.seconds_under(tr, "tsrc") / frames
            section["tsrc_us_per_frame_event_stats_only"] = 1e6 * plain_tsrc / frames
            section["scope_s"] = scopes.table(tr, SCOPES)
            section["top_ops"] = tracing.top_ops(tr, 15)
            section["stat_names"] = sorted({k for ev in tr.device.values() for e in ev for k in e.stats})
            examples = {}
            for e in sorted(next(iter(tr.device.values())), key=lambda e: e.t0 - e.t1)[:300]:
                op = tracing.op_name(e)
                if op not in examples and len(examples) < 8:
                    examples[op] = {k: str(v)[:240] for k, v in e.stats.items()}
            section["op_stats"] = examples
            rows, gap_s, shares = gap_table(tr, rec)
            section.update(gaps=rows, gaps_s=gap_s, gap_shares_pct=shares)
        line["profile_" + name] = section
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("the probe needs a TPU", file=sys.stderr)
        return 2
    from bench import harness
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.spec()
    cell = harness.workload(bench, args.workload)
    logdir = os.path.join(ROOT, ".bench", "probe", f"{args.workload}.{args.seed}")
    shutil.rmtree(logdir, ignore_errors=True)  # one profile per directory
    line = probe(
        harness.config(bench, cell["config"]), harness.traffic(cell["traffic"]),
        seed=args.seed, seconds=args.seconds, logdir=logdir,
    )
    line["workload"], line["seed"] = args.workload, args.seed
    print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
