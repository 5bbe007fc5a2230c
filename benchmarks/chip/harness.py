"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

A cell is found by name (``common.resolve_cell``); its traffic names the
driver (``drivers/<kind>.py``) that sets it up and drives its window, and
each per-layer metric is read by ``layer_metrics/<metric>.py``.  Adding a
configuration, a traffic mix, a cell or a metric adds files and edits
none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

from common import BENCH_DIR, CHECKOUT, CompileMeter, log, span
import trace_reduce as trace_mod


@dataclasses.dataclass
class Context:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    cell: Dict
    seed: int
    seconds: float
    devices: List
    arch: Any = None
    record: Dict = dataclasses.field(default_factory=dict)
    state: Dict = dataclasses.field(default_factory=dict)


def load_file_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_arch(config: Dict):
    """The program's registry configuration, held to the file's ``program``
    block: a registry entry that drifts from the file is an error."""
    from repro.configs import get_config
    arch = get_config(config["arch_id"])
    if config.get("arch_reduced"):
        arch = arch.reduced()
    if config.get("arch_overrides"):
        arch = dataclasses.replace(arch, **config["arch_overrides"])
    for key, want in config["program"].items():
        got = arch
        for part in key.split("."):
            got = getattr(got, part)
        if got != want:
            raise RuntimeError(f"the program runs {config['name']} with {key}="
                               f"{got!r}; its configuration file says {want!r}")
    return arch


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(spec: Dict, seed: int, seconds: float, trace: bool, devices: List,
             peak: Dict, t_start: float,
             wrap_driver: Optional[Callable] = None) -> Dict:
    """Run one cell on ``devices``; the result line as a dict.
    ``wrap_driver(module)`` lets a test break the timed path."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter(jax)
    ctx = Context(name=spec["name"], chips=spec["chips"], config=spec["config"],
                  traffic=spec["traffic"], cell=spec["cell"], seed=seed,
                  seconds=seconds, devices=list(devices))
    ctx.arch = program_arch(spec["config"])
    driver = load_file_module(os.path.join(BENCH_DIR, "drivers",
                                           spec["traffic"]["driver"] + ".py"))
    if wrap_driver is not None:
        driver = wrap_driver(driver)
    log(f"cell {ctx.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"devices={len(devices)} compile_cache={cache_dir}")
    t_setup = time.time()
    with span("setup"):
        driver.setup(ctx)
    log(f"set-up: {t_setup - t_start:.3f}s from process start to the driver "
        f"(imports, TPU start), {time.time() - t_setup:.3f}s in the driver")

    trace_dir = os.path.join(CHECKOUT, ".bench_trace", ctx.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)

    def opened():
        ann = span("window")
        ann.__enter__()
        ctx.record["t_open"] = time.perf_counter()

        def close():
            ctx.record["t_close"] = time.perf_counter()
            ann.__exit__(None, None, None)
        return close

    driver.window(ctx, opened)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = trace_mod.find_xplane(trace_dir)
        if path:
            reduced = trace_mod.reduce(trace_mod.load(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            top = sorted(reduced["modules"].items(), key=lambda kv: -kv[1]["seconds"])[:6]
            log("programs: " + "; ".join(f"{k} x{v['count']:.0f} {v['seconds']:.4f}s"
                                         for k, v in top))
    rec = ctx.record
    mem = memory_peak(ctx.devices)
    in_window = meter.count(rec["t_open"], rec["t_close"])
    rec["compile_s"] = meter.seconds(t1=rec["t_open"])
    log(f"compiles_in_window={in_window} compile_s_setup={rec['compile_s']:.3f} "
        f"cache_hits={meter.cache_hits} memory_peak_bytes={mem}")

    compared = driver.check(ctx)
    limits = spec["cell"]["limits"]
    missing = sorted(set(limits) - set(compared))
    if missing:
        raise RuntimeError(f"the comparison gave no {missing}")
    correct = all(compared[k] <= limits[k] for k in limits)

    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": mem}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": rec["attempted"],
                           "failed": rec["failed"]}
    if not trace:
        values = dict(rec["metrics"], setup_s=rec["t_window_start"] - t_start)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    else:
        rec.update(trace=reduced, peak=peak, config=ctx.config,
                   traffic=ctx.traffic, chips=ctx.chips)
        metrics = {}
        for m in spec["per_layer"]:
            reader = load_file_module(os.path.join(BENCH_DIR, "layer_metrics",
                                                   m["name"] + ".py"))
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            out["breakdown"] = {
                "device_ops": [[k, v] for k, v in reduced["device_ops"]],
                "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"]]}
    out["metrics"] = metrics
    out["device"] = device
    out["compared"] = {k: {"value": compared[k], "limit": limits[k]} for k in limits}
    for k in limits:
        log(f"compared {k} = {compared[k]!r} limit {limits[k]!r}")
    return out
