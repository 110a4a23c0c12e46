#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls
(``layers`` -> ``Program`` -> ``append_backward`` -> ``Executor.run`` /
``run_steps`` and the ``CompiledProgram`` mesh variant), at the full
width of ERNIE-base, with random weights from a seed:

    python3 chip_smoke.py                 # every phase; needs a TPU
    python3 chip_smoke.py four_chips      # attach + the named phases

One process, one attach (a chip belongs to one process at a time; this
script starts no process that could want it). Phases run in order and
the first failed phase ends the run: non-zero exit, the phase's own
error on stderr, no result line. Every stdout line is one JSON object —
one per passed phase, with what it saw, its compile seconds and its
persistent-cache hits — carrying ``platform``, ``device_kind`` and
``n_devices``; on success the last line is ``{"ok": true, "device":
{...}}``.

Without a TPU the run fails at ``attach``, naming the platform found.
``--rehearse-cpu`` is the caller's explicit choice of a tiny-size CPU
walk through the same phase code (Pallas kernels interpreted); it never
happens by failing to find a chip, and its lines say ``"platform":
"cpu"``.

Seconds printed here (compile, first call, step) are observations of a
smoke run — a few unrepeated steps — not benchmark numbers.
"""
import argparse
import json
import sys
import time
import traceback

import numpy as np

# what each phase runs at on the chip: ERNIE-base and the long-context
# GPT at full width and depth
CHIP_SIZES = dict(
    ernie=dict(batch=128, seq=128, preds=20, lr=1e-4, cfg=dict(
        dtype="bfloat16")),
    gpt=dict(batch=2, seq=4096, cfg=dict(
        vocab_size=32000, hidden_size=768, num_layers=12, num_heads=12,
        ff_size=3072, max_position=4096, dropout=0.0, dtype="bfloat16",
        attn_impl="flash", recompute=True)),
    fleet=dict(batch=256, seq=128, preds=20, lr=1e-4, cfg=dict(
        dtype="bfloat16", tp=True)),
    ring=dict(shape=(2, 12, 8192, 64), dtype="bfloat16", tol=2e-2))
# the rehearsal's sizes only have to reach every line of the phase code
REHEARSAL_SIZES = dict(
    ernie=dict(batch=8, seq=32, preds=4, lr=1e-3, cfg=dict(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=2,
        ff_size=128, max_position=64)),
    gpt=dict(batch=1, seq=256, cfg=dict(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
        ff_size=128, max_position=256, dropout=0.0, attn_impl="flash",
        recompute=True)),
    fleet=dict(batch=8, seq=32, preds=4, lr=1e-3, cfg=dict(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=2,
        ff_size=128, max_position=64, tp=True)),
    ring=dict(shape=(1, 2, 512, 64), dtype="float32", tol=1e-4))

RUN_STEPS = 10        # exe.run calls on the fixed batch (>= 9: 8 updates)
WINDOW = 4            # length of the run_steps window


class PhaseFailed(Exception):
    """A check of the running phase did not hold."""


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


class Run(object):
    """State of one smoke run: the device fields every line carries, the
    sizes in effect, and what the Executor's step-cache misses of a phase
    cost (`executor.miss_log()`: the tree's one set of compile listeners),
    so cold and warm runs can be told apart."""

    def __init__(self, rehearsal):
        self.rehearsal = rehearsal
        self.sizes = REHEARSAL_SIZES if rehearsal else CHIP_SIZES
        self.device = {}

    @staticmethod
    def compile_counters(since):
        """Of the Executor misses since `since` on obs's clock (the log
        keeps the last 32): trace + lower seconds, backend compile
        seconds, the persistent cache's hits, and the requests it did not
        answer. Compiles outside `Executor` (eager ops, a kernel called
        alone) are not in it."""
        from paddle_tpu.framework import executor
        log = [m for m in executor.miss_log() if m["t0"] >= since]
        return {
            "trace_lower_s": round(sum(m["trace_s"] + m["lower_s"]
                                       for m in log), 2),
            "compile_s": round(sum(m["backend_s"] for m in log), 2),
            "persistent_cache_hits": sum(m["cache_hits"] for m in log),
            "persistent_cache_misses": sum(
                m["cache_requests"] - m["cache_hits"] for m in log)}

    def place(self):
        import paddle_tpu as pt
        return pt.CPUPlace() if self.rehearsal else pt.TPUPlace(0)

    def emit(self, phase, **fields):
        line = {"phase": phase}
        line.update(self.device)
        line.update(fields)
        print(json.dumps(line), flush=True)


def _loss(fetched):
    return float(np.asarray(fetched).reshape(-1)[0])


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
def attach(run):
    """The one attach. Places the compile cache before JAX compiles
    anything, then requires the platform this invocation was asked for."""
    from importlib import metadata
    from paddle_tpu.framework.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if run.rehearsal else "tpu"
    if dev.platform != want:
        raise PhaseFailed(
            "chip_smoke.py needs platform %r; JAX found %r (%s x%d)%s"
            % (want, dev.platform, dev.device_kind, len(devices),
               "" if run.rehearsal else
               " — there is no CPU fallback; --rehearse-cpu is the "
               "explicit tiny-size walk-through"))
    run.device = {"platform": dev.platform, "device_kind": dev.device_kind,
                  "n_devices": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    from paddle_tpu.native import build as native_build
    built = native_build.native_available()
    return dict(jax=jax.__version__, jaxlib=metadata.version("jaxlib"),
                libtpu=libtpu, compile_cache_dir=cache_dir,
                rehearsal=run.rehearsal, native_dataplane_built=built,
                native_dataplane_error=None if built
                else repr(native_build.build_error()))


# ---------------------------------------------------------------------------
def _train_fixed_batch(phase, exe, program, feed, loss_var, steps):
    """`steps` exe.run calls on one fixed batch. Checks: every loss
    finite, the last below the first, no cache miss after the first
    call. Returns (losses, first_call_s, later step seconds)."""
    losses, secs = [], []
    misses_after_first = None
    for i in range(steps):
        out, dt = _timed(lambda: exe.run(program, feed=feed,
                                         fetch_list=[loss_var]))
        losses.append(_loss(out[0]))
        secs.append(dt)
        if i == 0:
            misses_after_first = exe.cache_misses
    check(np.isfinite(losses).all(), "%s: non-finite loss in %r"
          % (phase, losses))
    check(exe.cache_misses == misses_after_first,
          "%s: the step recompiled after its first call (cache misses "
          "%d -> %d)" % (phase, misses_after_first, exe.cache_misses))
    check(losses[-1] < losses[0],
          "%s: loss on the fixed batch did not fall in %d steps: %r"
          % (phase, steps, losses))
    return losses, secs[0], secs[1:]


def _on_device(arr, device):
    import jax
    return isinstance(arr, jax.Array) and set(arr.devices()) == {device}


def train_ernie_base(run):
    """ERNIE-base MLM+NSP pretraining through Executor on one chip."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.models import bert
    sz = run.sizes["ernie"]
    batch, seq, preds = sz["batch"], sz["seq"], sz["preds"]
    cfg = bert.bert_base(**sz["cfg"])
    adam = optimizer.Adam(sz["lr"])
    main, startup, _feeds, fetch = bert.bert_pretrain_program(
        cfg, batch, seq, preds, optimizer_fn=adam.minimize)
    loss = fetch["loss"]
    scope = Scope()
    with scope_guard(scope):
        place = run.place()
        exe = pt.Executor(place)
        _, startup_s = _timed(lambda: exe.run(startup))
        feed = bert.synthetic_batch(cfg, batch, seq, preds)
        losses, first_s, step_secs = _train_fixed_batch(
            "train_ernie_base", exe, main, feed, loss, RUN_STEPS)

        # one run_steps window (lax.scan over WINDOW distinct batches),
        # then the same window again: the second call must be a cache hit
        batches = [bert.synthetic_batch(cfg, batch, seq, preds, seed=1 + i)
                   for i in range(WINDOW)]
        stacked = {k: np.stack([b[k] for b in batches]) for k in feed}
        misses = exe.cache_misses
        win, win_first_s = _timed(lambda: exe.run_steps(
            main, feed=stacked, fetch_list=[loss]))
        check(exe.cache_misses == misses + 1,
              "run_steps window: expected exactly one new cache entry")
        win2, win_s = _timed(lambda: exe.run_steps(
            main, feed=stacked, fetch_list=[loss]))
        check(exe.cache_misses == misses + 1,
              "run_steps window recompiled on its second call")
        win_losses = [float(v) for v in np.asarray(win[0]).reshape(-1)] + \
            [float(v) for v in np.asarray(win2[0]).reshape(-1)]
        check(len(win_losses) == 2 * WINDOW and np.isfinite(win_losses).all(),
              "run_steps window losses %r" % (win_losses,))

        # state lives on the device the place names
        device = place.jax_device()
        param = scope.find_var("word_embedding")
        moment_name = adam._accumulators[("moment1", "word_embedding")].name
        moment = scope.find_var(moment_name)
        check(_on_device(param, device) and _on_device(moment, device),
              "state is not a jax.Array on %r: word_embedding %r, %s %r"
              % (device, type(param), moment_name, type(moment)))

        # which dropout RNG the step that ran was lowered with: read it
        # off the step's own StableHLO (the rbg path emits
        # rng_bit_generator; threefry lowers to plain integer arithmetic)
        lowered = exe.dump_hlo(main, feed=feed, fetch_list=[loss],
                               include_compiled=False)["lowered"]
        rng_path = "rbg" if "rng_bit_generator" in lowered else "threefry"
    return dict(
        batch=batch, seq=seq, preds=preds, hidden=cfg.hidden_size,
        layers=cfg.num_layers, dtype=cfg.dtype,
        losses=[round(v, 4) for v in losses],
        window_losses=[round(v, 4) for v in win_losses],
        dropout_rng=rng_path, state_device=str(device),
        startup_s=round(startup_s, 2), smoke_first_call_s=round(first_s, 2),
        smoke_step_s=round(float(np.median(step_secs)), 4),
        smoke_window_first_call_s=round(win_first_s, 2),
        smoke_window_s=round(win_s, 4), window=WINDOW)


# ---------------------------------------------------------------------------
def pallas_selfcheck(interpret):
    """Pallas-vs-XLA oracle, compiled by Mosaic on the chip — the only
    coverage of the compiled kernels: CPU tests run interpret
    mode and the <128-block guards route small shapes to XLA. Flash
    attention fwd + backward in every mask mode (causal, additive
    key-padding mask, per-query bias) at T=128/256, f32 and bf16, at
    the long-context shape (2, 12, 4096, 64) bf16, and with grouped heads,
    a value width of twice the q/k width and a sliding window (T=512, and
    T=4096 with a 512 window); the fused backward kernel against the
    dK/dV + dQ pair it stands for, each at its rule's tile (T=256, also
    at D 192 / Dv 128 in f32, and the GPT cells' (4, 12, 4096, 64) and
    (16, 12, 1024, 64) and the Kimi cells' (2, 16, 8192, 192 | 128) bf16:
    the calls above without grouped heads or a window already take the
    fused one against XLA); the selective-scan forward and backward
    kernels (T=320: not a multiple of the chunk), f32 and bf16; each fwd+bwd
    against its pure-JAX reference. Every check runs; one the compiler
    refuses (or that raises) is recorded with its message and fails the
    whole result.
    ``interpret=True`` (the rehearsal) runs the same checks through the
    Pallas interpreter — a CPU rehearsal of the check logic, not of
    Mosaic."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(0)
    checks = {}

    def compare(pairs, tol):
        abs_errs, rel_errs = [], []
        for a, b_ in pairs:
            a = jnp.asarray(a, jnp.float32)
            b_ = jnp.asarray(b_, jnp.float32)
            diff = float(jnp.max(jnp.abs(a - b_)))
            abs_errs.append(diff)
            # normalize by the oracle's dynamic range: a bf16 result is
            # only representable to ~0.4% of its magnitude, so absolute
            # error alone would flag 1-ulp differences on large grads
            rel_errs.append(diff / max(float(jnp.max(jnp.abs(b_))), 1.0))
        finite = all(np.isfinite(abs_errs))
        return {"max_abs_err": round(max(abs_errs), 8),
                "max_rel_err": round(max(rel_errs), 8), "tol": tol,
                "ok": finite and max(rel_errs) < tol}

    def run(key, fn):
        try:
            checks[key] = fn()
        except Exception as e:   # record every kernel's verdict, then fail
            checks[key] = {"ok": False, "error": "%s: %s" % (
                type(e).__name__, str(e)[-1500:]),
                "where": traceback.format_exc(limit=-3)[-600:]}

    def flash_case(dtype, tol, b, h, t, d, mode, hkv=None, dv=None,
                   window=None):
        hkv, dv = hkv or h, dv or d
        q = jnp.asarray(rng.randn(b, h, t, d), dtype)
        k = jnp.asarray(rng.randn(b, hkv, t, d), dtype)
        v = jnp.asarray(rng.randn(b, hkv, t, dv), dtype)
        scale = 1.0 / np.sqrt(d)
        # fixed random cotangent shared by both implementations
        w = jnp.asarray(rng.randn(b, h, t, dv).astype(np.float32))
        mask, causal = None, True
        if mode == "padmask":
            # additive padding mask: last quarter of keys masked out
            pad = np.zeros((b, 1, 1, t), np.float32)
            pad[..., 3 * t // 4:] = -1e9
            mask, causal = jnp.asarray(pad, dtype), False
        elif mode == "qkmask":
            # per-query additive bias (B, 1, Tq, Tk)
            mask, causal = jnp.asarray(rng.randn(b, 1, t, t), dtype), False

        def pallas_out(q, k, v):
            return fa.flash_attention(q, k, v, mask=mask, scale=scale,
                                      causal=causal, interpret=interpret,
                                      window=window)

        def xla_out(q, k, v):
            return fa._xla_attention(q, k, v, mask, scale, causal, window)

        def grads(out_fn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    out_fn(q, k, v).astype(jnp.float32) * w),
                argnums=(0, 1, 2)))(q, k, v)

        def check():
            return compare(
                [(jax.jit(pallas_out)(q, k, v), jax.jit(xla_out)(q, k, v))]
                + list(zip(grads(pallas_out), grads(xla_out))), tol)
        return check

    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)):
        for t in (128, 256):
            for mode in ("causal", "padmask", "qkmask"):
                run("flash_%s_T%d_%s" % (np.dtype(dtype).name, t, mode),
                    flash_case(dtype, tol, 2, 4, t, 64, mode))
    # grouped heads (4 query heads to 2 kv heads), a value width of twice
    # the q/k width and a sliding window: the differential-attention calls
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)):
        name = "flash_%s_T512_gqa_dv128" % np.dtype(dtype).name
        run(name + "_causal", flash_case(dtype, tol, 2, 4, 512, 64,
                                         "causal", hkv=2, dv=128))
        run(name + "_window128", flash_case(dtype, tol, 2, 4, 512, 64,
                                            "causal", hkv=2, dv=128,
                                            window=128))

    def fused_case(dtype, tol, b, h, t, d, dv=None, hkv=None, bd=None):
        """The fused backward against the split pair, each at its own
        tile: `hkv` key/value heads where they are fewer than `h` (the
        fused kernel then sums dK/dV over the group), `bd` the
        block-diffusion rule (L, T) over t = 2T rows in place of causal."""
        dv, hkv = dv or d, hkv or h
        q, k, v = (jnp.asarray(rng.randn(b, heads, t, width), dtype)
                   for heads, width in ((h, d), (hkv, d), (hkv, dv)))
        w = jnp.asarray(rng.randn(b, h, t, dv).astype(np.float32))

        def grads(kernels):
            blocks = tuple(fa.pick_blocks(t, t, d, dtype, kern, bd is None,
                                          dv=dv, block_diffusion=bd,
                                          group=h // hkv)
                           for kern in kernels)
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fa._flash(
                    q, k, v, None, 1.0 / np.sqrt(d), bd is None, blocks,
                    interpret, None, bd).astype(jnp.float32) * w),
                argnums=(0, 1, 2)))(q, k, v)

        def check():
            return compare(list(zip(grads(fa.FUSED_KERNELS),
                                    grads(fa.KERNELS))), tol)
        return check

    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)):
        run("flash_%s_T256_fused_vs_split" % np.dtype(dtype).name,
            fused_case(dtype, tol, 2, 4, 256, 64))
    # unequal widths (latent attention's decompressed heads) are fused too
    run("flash_float32_T256_d192_dv128_fused_vs_split",
        fused_case(jnp.float32, 1e-5, 2, 4, 256, 192, dv=128))
    # and grouped heads: dK/dV summed over the group in the one kernel
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)):
        name = "flash_%s_T256_group" % np.dtype(dtype).name
        run(name + "4_fused_vs_split",
            fused_case(dtype, tol, 2, 8, 256, 64, hkv=2))
        run(name + "2_dv128_bd4_fused_vs_split",
            fused_case(dtype, tol, 1, 4, 256, 64, dv=128, hkv=2,
                       bd=(4, 128)))
    if not interpret:   # the interpreter needs minutes at these sizes
        run("flash_bfloat16_T4096_causal",
            flash_case(jnp.bfloat16, 1e-2, 2, 12, 4096, 64, "causal"))
        run("flash_bfloat16_T4096_gqa_dv128_window512",
            flash_case(jnp.bfloat16, 1e-2, 2, 4, 4096, 64, "causal", hkv=2,
                       dv=128, window=512))
        for b, t in ((4, 4096), (16, 1024)):    # the GPT cells' calls
            run("flash_bfloat16_%dx12x%dx64_fused_vs_split" % (b, t),
                fused_case(jnp.bfloat16, 1e-2, b, 12, t, 64))
        # the two Kimi cells' latent-attention call
        run("flash_bfloat16_2x16x8192x192_dv128_fused_vs_split",
            fused_case(jnp.bfloat16, 1e-2, 2, 16, 8192, 192, dv=128))
        # the five cells' grouped calls: SDAR's under its rule,
        # SmallThinker's global layer, LFM2's, Nemotron's, Phi's full layer
        for b, h, hkv, t, d, dv, bd in (
                (1, 32, 4, 16384, 128, None, (4, 8192)),
                (2, 28, 4, 16384, 128, None, None),
                (2, 32, 8, 8192, 64, None, None),
                (2, 32, 2, 8192, 128, None, None),
                (2, 20, 10, 8192, 64, 128, None)):
            run("flash_bfloat16_%dx%d:%dx%dx%d%s%s_fused_vs_split" % (
                b, h, hkv, t, d, "_dv%d" % dv if dv else "",
                "_bd%d" % bd[0] if bd else ""),
                fused_case(jnp.bfloat16, 1e-2, b, h, t, d, dv=dv, hkv=hkv,
                           bd=bd))

    def scan_case(dtype, tol, b, t, e, n):
        from paddle_tpu.ops.pallas import selective_scan as ss
        args = (jnp.asarray(rng.randn(b, t, e), dtype),
                jnp.asarray(jax.nn.softplus(rng.randn(b, t, e)), dtype),
                -jnp.exp(jnp.asarray(0.5 * rng.randn(e, n), jnp.float32)),
                jnp.asarray(rng.randn(b, t, n), dtype),
                jnp.asarray(rng.randn(b, t, n), dtype),
                jnp.asarray(rng.randn(e), jnp.float32))
        w = jnp.asarray(rng.randn(b, t, e).astype(np.float32))

        def both(fn):
            out = jax.jit(fn)(*args)
            grads = jax.jit(jax.grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                argnums=tuple(range(6))))(*args)
            return [out] + list(grads)

        def check():
            return compare(list(zip(
                both(lambda *a: ss.selective_scan(*a, interpret=interpret)),
                both(ss.scan_xla))), tol)
        return check

    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)):
        run("ssm_scan_%s" % np.dtype(dtype).name,
            scan_case(dtype, tol, 2, 320, 512, 16))

    return {"metric": "pallas_check", "interpret": bool(interpret),
            "checks": checks,
            "ok": all(c["ok"] for c in checks.values())}


def kernels(run):
    """The Pallas-vs-XLA oracle, compiled by Mosaic on the chip."""
    result = pallas_selfcheck(interpret=run.rehearsal)
    failed = {k: c for k, c in result["checks"].items() if not c["ok"]}
    check(result["ok"], "kernels: %d of %d checks failed:\n%s" % (
        len(failed), len(result["checks"]),
        "\n".join("--- %s: %s" % (k, json.dumps(c, indent=1))
                  for k, c in failed.items())))
    # per check: its max error relative to the oracle's range
    return dict(interpret=result["interpret"], checks=len(result["checks"]),
                max_rel_err={k: c["max_rel_err"]
                             for k, c in result["checks"].items()})


# ---------------------------------------------------------------------------
def train_gpt_flash(run):
    """GPT causal LM at T=4096 through the flash kernel inside a whole
    fused train step — the ERNIE step at T=128 never reaches Pallas."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.framework import obs
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.models import gpt
    sz = run.sizes["gpt"]
    batch, seq = sz["batch"], sz["seq"]
    cfg = gpt.GPTConfig(**sz["cfg"])
    main, startup, _feeds, fetch = gpt.gpt_pretrain_program(
        cfg, batch, seq, optimizer_fn=optimizer.Adam(1e-4).minimize)
    loss = fetch["loss"]
    feed = gpt.synthetic_batch(cfg, batch, seq)
    with scope_guard(Scope()):
        exe = pt.Executor(run.place())
        exe.run(startup)
        losses, first_s, step_secs = _train_fixed_batch(
            "train_gpt_flash", exe, main, feed, loss, 3)
        # the lowering made for the dump runs with obs on, so that it
        # leaves its `head.plan` (the timed steps above ran with obs off)
        obs.enable()
        try:
            lowered = exe.dump_hlo(main, feed=feed, fetch_list=[loss],
                                   include_compiled=False)["lowered"]
            head_plans = [p["labels"] for p in obs.spans(name="head.plan")]
        finally:
            obs.disable()
            obs.clear()
    check([p["form"] for p in head_plans] == ["weighted"]
          and head_plans[0]["blocks"] * head_plans[0]["block_rows"]
          == batch * seq,
          "train_gpt_flash: the head did not lower once in its weighted "
          "form over all %d rows: %r" % (batch * seq, head_plans))
    mosaic_calls = lowered.count("tpu_custom_call")
    # interpreted kernels lower to plain HLO: nothing to find off-chip
    check(run.rehearsal or mosaic_calls > 0,
          "train_gpt_flash: no Mosaic custom call in the lowered step — "
          "attention did not go through the Pallas kernel")
    return dict(
        batch=batch, seq=seq, hidden=cfg.hidden_size, layers=cfg.num_layers,
        dtype=cfg.dtype, losses=[round(v, 4) for v in losses],
        mosaic_custom_calls=mosaic_calls, head_plan=head_plans[0],
        smoke_first_call_s=round(first_s, 2),
        smoke_step_s=round(float(np.median(step_secs)), 4))


# ---------------------------------------------------------------------------
def four_chips(run):
    """The README's fleet path on a dp2 x mp2 mesh, then ring attention
    over sp=4 against single-chip flash. Runs when >= 4 devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    if run.device["n_devices"] < 4:
        return dict(ran=False, reason="needs >= 4 devices, found %d"
                    % run.device["n_devices"])
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import fleet, DistributedStrategy
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.ring_attention import ring_attention
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    devices = jax.devices()[:4]

    sz = run.sizes["fleet"]
    batch, seq, preds = sz["batch"], sz["seq"], sz["preds"]
    strategy = DistributedStrategy()
    strategy.mesh_axes = {"dp": 2, "mp": 2}
    fleet.init(strategy=strategy)
    try:
        cfg = bert.bert_base(**sz["cfg"])
        opt = fleet.distributed_optimizer(optimizer.Adam(sz["lr"]))
        main, startup, _feeds, fetch = bert.bert_pretrain_program(
            cfg, batch, seq, preds, optimizer_fn=opt.minimize)
        loss = fetch["loss"]
        compiled = fleet.main_program_compiled(main)
        scope = Scope()
        with scope_guard(scope):
            exe = pt.Executor(run.place())
            exe.run(startup)
            feed = bert.synthetic_batch(cfg, batch, seq, preds)
            losses, first_s, step_secs = _train_fixed_batch(
                "four_chips", exe, compiled, feed, loss, 5)
            # an mp-annotated weight: (hidden, ff) split on its ff dim
            w_name = "encoder_layer_0_ffn_fc_0.w_0"
            w = scope.find_var(w_name)
            shard_devices = {s.device for s in w.addressable_shards}
            shard_shapes = {tuple(s.data.shape)
                            for s in w.addressable_shards}
            check(shard_devices == set(devices),
                  "%s sits on %r, expected the four mesh devices"
                  % (w_name, sorted(map(str, shard_devices))))
            check(shard_shapes == {(cfg.hidden_size, cfg.ff_size // 2)},
                  "%s shards are %r, expected half-width (%d, %d)"
                  % (w_name, shard_shapes, cfg.hidden_size,
                     cfg.ff_size // 2))
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in devices]
    finally:
        mesh_mod.reset_mesh()
    if None in in_use:
        check(run.rehearsal, "memory_stats() reports no bytes_in_use: %r"
              % (in_use,))
    else:
        # nothing piled on chip 0: every chip holds state, same order
        check(min(in_use) > 0 and max(in_use) < 2 * min(in_use),
              "device memory in use is uneven across the mesh: %r"
              % (in_use,))

    rs = run.sizes["ring"]
    b, h, t, d = rs["shape"]
    dtype = jnp.dtype(rs["dtype"])
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(b, h, t, d), dtype) for _ in range(3)]
    sp_mesh = Mesh(np.array(devices), ("sp",))
    scale = 1.0 / np.sqrt(d)
    ring, ring_first_s = _timed(lambda: jax.block_until_ready(jax.jit(
        lambda q, k, v: ring_attention(
            q, k, v, mesh=sp_mesh, axis_name="sp", causal=True,
            scale=scale))(q, k, v)))
    flash = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, scale=scale, causal=True,
        interpret=run.rehearsal))(q, k, v)
    ring32 = np.asarray(ring.astype(jnp.float32))
    flash32 = np.asarray(flash.astype(jnp.float32))
    check(np.isfinite(ring32).all(), "ring attention output not finite")
    err = float(np.max(np.abs(ring32 - flash32)) /
                max(float(np.max(np.abs(flash32))), 1.0))
    check(err < rs["tol"],
          "ring attention over sp=4 differs from single-chip flash: max "
          "rel err %.3g >= %.3g" % (err, rs["tol"]))
    ring_devices = {s.device for s in ring.addressable_shards}
    check(ring_devices == set(devices),
          "ring attention output sits on %r"
          % sorted(map(str, ring_devices)))
    return dict(
        ran=True, mesh={"dp": 2, "mp": 2}, batch=batch, seq=seq,
        hidden=cfg.hidden_size, layers=cfg.num_layers, dtype=cfg.dtype,
        losses=[round(v, 4) for v in losses], sharded_weight=w_name,
        shard_shape=list(shard_shapes)[0],
        shard_devices=sorted(map(str, shard_devices)), bytes_in_use=in_use,
        smoke_first_call_s=round(first_s, 2),
        smoke_step_s=round(float(np.median(step_secs)), 4),
        ring_shape=[b, h, t, d], ring_dtype=rs["dtype"],
        ring_vs_flash_max_rel_err=round(err, 6),
        smoke_ring_first_call_s=round(ring_first_s, 2))


# ---------------------------------------------------------------------------
# phase name -> function(run) -> the fields of its line, in run order
PHASES = {"attach": attach, "train_ernie_base": train_ernie_base,
          "kernels": kernels, "train_gpt_flash": train_gpt_flash,
          "four_chips": four_chips}


def main(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("phases", nargs="*", metavar="phase",
                        help="phases to run after attach (default: all of "
                        "%s)" % ", ".join(list(PHASES)[1:]))
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny-size CPU walk-through of the phase code")
    args = parser.parse_args(argv)
    unknown = sorted(set(args.phases) - set(PHASES))
    if unknown:
        parser.error("unknown phase(s) %s" % ", ".join(unknown))
    from paddle_tpu.framework import obs
    run = Run(args.rehearse_cpu)
    for name, phase in PHASES.items():
        if name != "attach" and args.phases and name not in args.phases:
            continue
        since = obs.now()
        t0 = time.perf_counter()
        try:
            fields = phase(run)
        except Exception:   # the phase's own error, then a non-zero exit
            sys.stderr.write("chip_smoke: phase %s FAILED after %.1fs\n%s\n"
                             % (name, time.perf_counter() - t0,
                                traceback.format_exc()))
            return 1
        run.emit(name, ok=True, phase_s=round(time.perf_counter() - t0, 2),
                 **dict(run.compile_counters(since), **fields))
    result = {"ok": True, "device": {
        "platform": run.device["platform"],
        "kind": run.device["device_kind"],
        "count": run.device["n_devices"]}}
    if run.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
