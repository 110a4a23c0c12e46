"""One run of one cell: attach, build the program through paddle_tpu's
normal path, seed its weights, drive the compiled step through its first
steps (the output check's readings, and the warm-up), measure the window
with that same object, then follow the same steps with the plain reference
and decide `correct`.

`run.py` is the only entry that measures; it asks for platform "tpu".
The tests call `run_cell` with platform "cpu" at a tiny size.
"""
import gc
import json
import math
import os
import shutil
import time

import numpy as np

from benchmark import cells, flops, reference, stats, trace_reduce, weights

TRACE_DIR = ".bench_trace"      # inside the checkout, git-ignored
TRACE_WARM_STEPS = 3            # window steps before the profiler starts


class Refused(Exception):
    """The run may not measure here (no chip, too few chips)."""


def attach(platform, chips):
    """The one attach: compile cache inside the checkout first, then the
    devices. Refuses any platform but the one asked for."""
    from paddle_tpu.framework.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused("the benchmark needs platform %r; JAX found %r (%s x%d)"
                      % (platform, devices[0].platform,
                         devices[0].device_kind, len(devices)))
    if len(devices) < chips:
        raise Refused("the cell asks for %d chips; JAX found %d"
                      % (chips, len(devices)))
    return devices[:chips], cache_dir


class Runner(object):
    """The compiled step with its state: built once, checked, then timed."""

    def __init__(self, cell, devices):
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.framework.scope import Scope
        self.cell, self.devices = cell, devices
        cfg, traffic = cell.config, cell.traffic
        opt = cfg["optimizer"]
        if opt["name"] != "adam":
            raise ValueError("only adam is wired, not %r" % opt["name"])
        adam = optimizer.Adam(learning_rate=opt["learning_rate"],
                              beta1=opt["beta1"], beta2=opt["beta2"],
                              epsilon=opt["epsilon"])
        self.mesh = None
        mesh_axes = traffic.get("mesh_axes")
        if mesh_axes:
            from paddle_tpu.distributed import fleet, DistributedStrategy
            from paddle_tpu.distributed import mesh as mesh_mod
            if cell.mesh_size() != len(devices):
                raise ValueError("mesh %r does not span the cell's %d chips"
                                 % (mesh_axes, len(devices)))
            strategy = DistributedStrategy()
            strategy.mesh_axes = dict(mesh_axes)
            fleet.init(strategy=strategy)
            self.mesh = mesh_mod.get_mesh()
            dist = fleet.distributed_optimizer(adam)
            main, startup, loss = cell.family.build(cfg, traffic,
                                                    dist.minimize)
            self.program = fleet.main_program_compiled(main)
        else:
            main, startup, loss = cell.family.build(cfg, traffic,
                                                    adam.minimize)
            self.program = main
        self.main, self.loss = main, loss
        self.scope = Scope()
        place = pt.TPUPlace(0) if devices[0].platform == "tpu" \
            else pt.CPUPlace()
        self.exe = pt.Executor(place)
        self.exe.run(startup, scope=self.scope)
        self.specs = cell.family.param_specs(cfg, traffic)
        self._check_specs()
        self._make_weights = weights.weight_maker(
            self.specs, cfg["initializer_range"], self._sharding())

    def free_state(self):
        """Drop the program's state and compiled steps, so that the
        reference has the chip's memory to itself."""
        self.scope = None
        self.exe.close()
        gc.collect()

    def close(self):
        if self.mesh is not None:
            from paddle_tpu.distributed import mesh as mesh_mod
            mesh_mod.reset_mesh()
        self.exe.close()

    def _check_specs(self):
        params = {p.name: p for p in
                  self.main.global_block().all_parameters()}
        if set(params) != set(self.specs):
            raise ValueError("the family's parameter list and the program "
                             "differ: %s" % sorted(set(params)
                                                   ^ set(self.specs)))
        for name, (shape, dtype, _kind) in self.specs.items():
            have = self.scope.find_var(name)
            if tuple(have.shape) != tuple(shape) or str(have.dtype) != dtype:
                raise ValueError(
                    "%s: the program holds %s %s, the family lists %s %s"
                    % (name, have.shape, have.dtype, shape, dtype))

    def _sharding(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def seeded_weights(self, seed):
        return self._make_weights(seed)

    def reset(self, seed):
        """Seeded weights into the scope, optimizer state back to its
        start: the state a run begins from."""
        import jax.numpy as jnp
        for name, value in self.seeded_weights(seed).items():
            self.scope.set_var(name, value)
        opt = self.cell.config["optimizer"]
        for name in list(self.scope.keys()):
            var = self.scope.find_var(name)
            if var is None:
                continue
            if "_moment1_" in name or "_moment2_" in name:
                self.scope.set_var(name, jnp.zeros(var.shape, var.dtype))
            elif "_beta1_pow_acc_" in name:
                self.scope.set_var(name, jnp.full(var.shape, opt["beta1"],
                                                  var.dtype))
            elif "_beta2_pow_acc_" in name:
                self.scope.set_var(name, jnp.full(var.shape, opt["beta2"],
                                                  var.dtype))

    def step(self, batch):
        """The window's own call and feed: one blocking exe.run, numpy in,
        numpy loss back."""
        out = self.exe.run(self.program, feed=batch,
                           fetch_list=[self.loss], scope=self.scope)
        return float(out[0].reshape(-1)[0])

    def check_steps(self, seed, batches):
        """Drive the step through the first CHECK_STEPS batches and read
        what `correct` compares: each loss, the first gradient as the
        optimizer got it (from Adam's first moment after one step:
        m1 = (1-b1) g; brought to the host, so that the window's memory is
        the program's alone) with its norm per leaf, and the norm of each
        leaf's change after the steps."""
        b1 = self.cell.config["optimizer"]["beta1"]
        losses, first = [], None
        for i in range(reference.CHECK_STEPS):
            losses.append(self.step(batches[i]))
            if i == 0:
                first = {k: np.asarray(m, np.float32) / np.float32(1.0 - b1)
                         for k, m in self._first_moments().items()}
        now = {n: self.scope.find_var(n) for n in self.specs}
        deltas = reference.delta_norms(now, self.seeded_weights(seed))
        return {"losses": losses, "first_gradient": first,
                "grad_norms": {k: float(np.sqrt(np.sum(np.square(
                    g, dtype=np.float64)))) for k, g in first.items()},
                "delta_norms": {k: float(v) for k, v in deltas.items()}}

    def _first_moments(self):
        """{parameter: its Adam first moment}, found by the accumulator's
        name (`<parameter>_moment1_<n>`)."""
        found = {}
        for name in self.scope.keys():
            head, sep, tail = name.rpartition("_moment1_")
            if sep and tail.isdigit() and head in self.specs:
                found[head] = self.scope.find_var(name)
        if set(found) != set(self.specs):
            raise ValueError("no first moment for %s" % sorted(
                set(self.specs) - set(found)))
        return found

    def state_bytes_fullest(self):
        """Bytes of the persistable scope arrays on the fullest device."""
        per_dev = {}
        for name in self.scope.keys():
            var = self.scope.find_var(name)
            for shard in getattr(var, "addressable_shards", ()):
                per_dev[shard.device] = per_dev.get(shard.device, 0) \
                    + shard.data.nbytes
        return max(per_dev.values()) if per_dev else 0


def make_pool(cell, seed):
    rng = weights.host_rng(seed, 1)
    return [cell.family.make_batch(cell.config, cell.traffic, rng)
            for _ in range(cell.traffic["pool_batches"])]


GAPS = ("loss_gap", "grad_diff", "grad_norm_gap", "delta_norm_gap")


def compare(program, ref, limits, who="program"):
    """The numbers compared, each beside its limit:
    [(name, value, limit, ok, note)]. `grad_diff` is the worst leaf's norm
    of (the first gradient minus the reference's), `grad_norm_gap` and
    `delta_norm_gap` the worst leaf's gap between the two sides' norms;
    each against the reference's norm of that leaf or of the median leaf."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["losses"], ref["losses"]))
    diff, diff_leaf = stats.worst_leaf_gap(
        program["grad_norms"], ref["grad_norms"],
        ref["grad_diff_norms"][who])
    grad_gap, grad_leaf = stats.worst_leaf_gap(program["grad_norms"],
                                               ref["grad_norms"])
    delta_gap, delta_leaf = stats.worst_leaf_gap(program["delta_norms"],
                                                 ref["delta_norms"])
    rows = [("loss_gap", loss_gap, "losses %r vs reference %r"
             % (program["losses"], ref["losses"])),
            ("grad_diff", diff, "worst leaf %s" % diff_leaf),
            ("grad_norm_gap", grad_gap, "worst leaf %s" % grad_leaf),
            ("delta_norm_gap", delta_gap, "worst leaf %s" % delta_leaf)]
    return [(name, value, limits[name], bool(value <= limits[name]), note)
            for name, value, note in rows]


def reference_numbers(cell, runner, seed, pool, precision="float32", **kw):
    start = weights.as_float32(runner.seeded_weights(seed))
    return reference.follow(cell.family, cell.config, cell.traffic, start,
                            pool, precision, mesh=runner.mesh, **kw)


def device_block(devices, traced=None):
    """`memory_peak_bytes` is the fullest chip's `peak_bytes_in_use` (live
    buffers) plus its `peak_bytes_reserved`: the v5e runtime reserves the
    loaded programs' temporary space apart from live buffers, and both have
    to fit the chip (PERF.md, Findings)."""
    peak = 0
    for d in devices:
        stats_ = d.memory_stats() or {}
        peak = max(peak, int(stats_.get("peak_bytes_in_use", 0))
                   + int(stats_.get("peak_bytes_reserved", 0)))
    block = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices), "memory_peak_bytes": peak}
    if traced is not None:
        block["busy_s"] = traced["busy_s"]
        block["window_s"] = traced["window_s"]
    return block


def run_cell(workload, seed, seconds, trace, platform="tpu",
             root=cells.ROOT, t_start=None, say=print, broken=None):
    """One run. Returns the result object (the caller prints it as the
    last line). `broken` is the tests' hook to break the timed path
    underneath: a function that takes the Runner before its first step."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cells.Cell(workload, root)
    devices, cache_dir = attach(platform, cell.chips)
    say("device platform=%s kind=%r count=%d compile_cache=%s"
        % (devices[0].platform, devices[0].device_kind, len(devices),
           cache_dir))
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    say("device bytes_limit=%s" % limit)
    peaks = flops.peaks_for(devices[0].device_kind) \
        if platform == "tpu" else None

    marks = [("attach", time.perf_counter())]
    pool = make_pool(cell, seed)
    marks.append(("batches", time.perf_counter()))
    runner = Runner(cell, devices)
    marks.append(("program build + startup", time.perf_counter()))
    try:
        if broken is not None:
            broken(runner)
        runner.reset(seed)
        marks.append(("seeded weights", time.perf_counter()))
        program_numbers = runner.check_steps(seed, pool)
        marks.append(("first %d steps (compile or cache load) + the output "
                      "check's readings" % reference.CHECK_STEPS,
                      time.perf_counter()))
        for i in range(cell.traffic["warmup_steps"]):
            runner.step(pool[(reference.CHECK_STEPS + i) % len(pool)])
        state_bytes = runner.state_bytes_fullest()
        marks.append(("warm-up", time.perf_counter()))
        say("setup split: " + ", ".join(
            "%s %.1f s" % (label, t - prev) for (label, t), prev in
            zip(marks, [t_start] + [t for _l, t in marks])))

        window = _window(runner, pool, seconds, trace, cell, t_start, say)
        result_device = device_block(devices, window.get("traced"))
        say("memory_stats %r" % (devices[0].memory_stats(),))
        runner.free_state()

        t0 = time.perf_counter()
        ref = reference_numbers(
            cell, runner, seed, pool,
            compare_with={"program": program_numbers["first_gradient"]})
        say("output check: reference followed %d steps in %.1f s (after the "
            "window, not in setup_s)" % (reference.CHECK_STEPS,
                                         time.perf_counter() - t0))
    finally:
        runner.close()

    rows = compare(program_numbers, ref, cell.limits)
    for name, value, lim, ok, note in rows:
        say("check %s %.6g limit %.6g %s (%s)"
            % (name, value, lim, "ok" if ok else "FAILED", note))
    finite = all(math.isfinite(x) for x in window["losses"])
    say("check window_losses_finite %s over %d steps (last loss %.4f)"
        % (finite, len(window["losses"]), window["losses"][-1]))
    say("check recompiles_in_window %d limit 0" % window["recompiles"])
    correct = bool(all(r[3] for r in rows) and finite
                   and window["recompiles"] == 0)

    record = dict(window, cell=cell, chips=len(devices), peaks=peaks,
                  state_bytes=state_bytes,
                  train_flops=cell.family.train_flops(cell.config,
                                                      cell.traffic))
    metrics = _per_layer(cell, record, say) if trace \
        else _end_to_end(cell, record, result_device)
    result = {"correct": correct, "attempted": len(window["losses"]),
              "failed": sum(1 for x in window["losses"]
                            if not math.isfinite(x)),
              "metrics": metrics, "device": result_device}
    if trace and window.get("traced"):
        result["breakdown"] = {
            "device_ops": window["traced"]["device_ops"],
            "idle_gaps": window["traced"]["idle_gaps"]}
    return result


def _window(runner, pool, seconds, trace, cell, t_start, say):
    """The measured window: blocking steps for `seconds`, nothing else in
    the process. In a traced run obs is on and the profiler covers
    `trace_steps` steady steps inside the window."""
    import jax
    from paddle_tpu.framework import obs
    exe = runner.exe
    step_ms, losses = [], []
    n_pool = len(pool)
    trace_dir = os.path.join(cell.root, TRACE_DIR, cell.name)
    traced_steps = int(cell.traffic["trace_steps"]) if trace else 0
    trace_from = TRACE_WARM_STEPS if trace else -1
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        obs.clear()
        obs.enable()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
    annotate = jax.profiler.TraceAnnotation
    span = None
    gc.collect()
    gc.freeze()
    gc.disable()
    misses0 = exe.cache_misses
    try:
        i = 0
        t_first = time.perf_counter()
        while True:
            if i == trace_from:
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                span = annotate("bench.traced")
                span.__enter__()
            t0 = time.perf_counter()
            if span is not None:
                with annotate("bench.exe_run"):
                    loss = runner.step(pool[i % n_pool])
            else:
                loss = runner.step(pool[i % n_pool])
            t1 = time.perf_counter()
            step_ms.append((t1 - t0) * 1e3)
            losses.append(loss)
            i += 1
            if span is not None and i == trace_from + traced_steps:
                span.__exit__(None, None, None)
                span = None
                jax.profiler.stop_trace()
            if t1 - t_first >= seconds and span is None:
                break
        t_last = t1
    finally:
        gc.enable()
        gc.unfreeze()
        if trace:
            obs.disable()
    out = {"losses": losses, "step_ms": step_ms,
           "setup_s": t_first - t_start, "first_dispatch_s": t_first,
           "last_completion_s": t_last,
           "recompiles": exe.cache_misses - misses0,
           "tokens_per_step": cell.traffic["tokens_per_step"]}
    slowest = sorted(range(len(step_ms)), key=step_ms.__getitem__)[-3:]
    say("window steps=%d seconds=%.3f step_ms median=%.3f; slowest steps "
        "%s" % (len(step_ms), t_last - t_first,
                stats.percentile(step_ms, 50),
                ", ".join("#%d %.1f ms" % (i, step_ms[i])
                          for i in reversed(slowest))))
    if trace:
        out["obs_spans"] = obs.spans()
        raw = trace_reduce.read_xplane(trace_dir)
        out["raw_trace"] = raw
        if not raw["devices"] and runner.devices[0].platform != "tpu":
            say("trace: no device plane off the TPU; the trace's metrics "
                "are left out")
            out["traced"] = None
            return out
        out["traced"] = trace_reduce.reduce_trace(raw, chips=len(
            runner.devices))
        say("trace window_s=%.3f busy_s=%.3f steps_seen=%d"
            % (out["traced"]["window_s"], out["traced"]["busy_s"],
               out["traced"]["steps_seen"]))
    return out


def _end_to_end(cell, record, device):
    """The cell's end-to-end metrics, taken by the benchmark itself."""
    values = {
        "tokens_per_s_per_chip": (stats.tokens_per_s_per_chip(
            len(record["step_ms"]), record["tokens_per_step"],
            record["first_dispatch_s"], record["last_completion_s"],
            record["chips"]), "tokens/s/chip"),
        "step_ms_p90": (stats.percentile(record["step_ms"], 90), "ms"),
        "peak_hbm_gib": (device["memory_peak_bytes"] / 2.0 ** 30, "GiB"),
        "setup_s": (record["setup_s"], "s"),
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise KeyError("the harness takes no end-to-end metric %r"
                           % m["name"])
        value, unit = values[m["name"]]
        out[m["name"]] = {"value": value, "unit": unit}
    return out


def _per_layer(cell, record, say):
    """Each per-layer metric through its own reader; one that finds
    nothing to read is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.layer_reader(m["name"]).read(record)
        if value is None:
            say("per-layer %s: nothing to read" % m["name"])
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv, platform, t_start):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      platform=platform, t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0
