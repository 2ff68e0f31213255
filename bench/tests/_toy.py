"""A toy cell for CPU rehearsals of the harness: the shapes of a served
cell shrunk until the whole run, reference included, takes seconds."""

import json
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def config():
    cfg = load("configs/glasses1m.json")
    cfg.update(frame=32, capacity=8, window=16, chunk_frames=4, k_ladder=[8, 16], backend="ref")
    return cfg


def traffic(loop="closed"):
    mix = load("traffic/walk.backfill.s8.json")
    mix.update(clip_frames=12, clips_max=2, streams=2, warmup_ticks=2, sample_streams=2)
    if loop == "open":
        mix.update(loop="open", warmup_ticks=2)
    return mix


def run(harness, tmp_path, before_window=None, loop="closed", seconds=0.6, seed=2**33 + 7,
        path=None):
    cfg = config()
    if path is not None:
        cfg["path"] = path
    if loop == "open":
        cfg["fps"] = 40
    e2e = [
        {"name": n, "unit": u}
        for n, u in (("frames_per_s", "frames/s"), ("setup_s", "s"),
                     ("latency_p95_ms", "ms"), ("latency_p50_ms", "ms"))
    ]
    return harness.run(
        cfg, traffic(loop), seed=seed, seconds=seconds, trace=False, e2e=e2e,
        per_layer=[], t_start=time.monotonic(), logdir=str(tmp_path / "trace"),
        before_window=before_window,
    )
