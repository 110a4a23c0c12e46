"""Phi-4-mini-flash-reasoning causal LM training (SambaY with differential
attention, arXiv:2507.06607): the program through paddle_tpu's normal path,
the seeded batch generator, the required-FLOPs count, the parameter list and
the plain reference.

The equations (program and reference implement exactly these; d = hidden,
E = ssm inner width, N = state size, R = dt rank, K = conv width, W = window):
every layer is h = x + Mixer(LN1(x)), x' = h + MLP(LN2(h)), MLP(u) =
(silu(u Wg) * (u Wu)) Wd with [Wg, Wu] one (d, 2F) matrix, no bias. Mixers
by `layer_kinds`:
  mamba / memory  [xs, z] = u Win; xc = silu(conv1d_causal(xs; w, b));
                  [dr, B, C] = xc Wx; delta = softplus(dr Wdt + bdt);
                  A = -exp(Alog); h_t = exp(delta_t x A) * h_{t-1}
                  + (delta_t * xc_t) x B_t; y_t = h_t C_t + D * xc_t;
                  out = (y * silu(z)) Wout. "memory" keeps y as M.
  gmu             out = (M * silu(u W1)) W2.
  window / full / cross   differential grouped attention: query heads pair
                  up (q_2j, q_2j+1), key heads (k_2g, k_2g+1), values
                  concatenate v_g = [v_2g, v_2g+1]; pair j reads group
                  g = j // (pairs per group); P1_j = softmax(mask(q_2j k_2g^T
                  / sqrt(dh))), P2_j with the odd heads; o_j = (P1_j - lam
                  P2_j) v_g; lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init,
                  lam_init = 0.8 - 0.6 exp(-0.3 i), i the PUBLISHED layer
                  index; o_j <- RMSNorm(o_j; gamma) (1 - lam_init); out =
                  concat(o) Wo + bo. Mask: causal; "window" also hides keys
                  s <= t - W. "full" keeps its K, V as K*, V*; "cross" has
                  its own Wq, Wo and reads K*, V*.
Head: logits = LNf(x) Emb^T over the rows held; loss = mean cross-entropy
over every position.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the scan is a `lax.scan` over time, attention
explicit scores per head pair. It is BLOCKED so that at the cell's size it
fits beside `reference.follow`'s five float32 copies of the parameters (20
bytes a parameter): `jax.checkpoint` per layer and again per mixer and MLP,
token chunks for the MLP and the head, query blocks per head pair, time
chunks with checkpoint for the scan. Blocking changes no value: every chunk
computes the same numbers the unblocked expression would.

Departures from the published model (the configuration file lists them):
seeded weights come in three kinds only (normal of `initializer_range`, ones,
zeros), so Alog and the lambdas are normal(0.02) (A ~ -1), the dt bias is
zero (delta ~ 0.69) and D is ones; block matrices in bfloat16 without a
float32 master copy; plain Adam; synthetic uniform tokens.
"""
import functools
import math

import numpy as np

from benchmark import flops_hybrid
from benchmark import reference as ref

MLP_CHUNK = 512         # tokens a block of the MLP and of the head holds
Q_BLOCK = 512           # queries a block of one head pair's scores holds
SCAN_CHUNK = 128        # time steps under one checkpoint of the scan
MAMBA_KINDS = ("mamba", "memory")
ATTN_KINDS = ("window", "full", "cross")


def sizes(config):
    """The widths every function here needs, with the assumed ones."""
    d = config["hidden_size"]
    return {"d": d, "ff": config["intermediate_size"],
            "hq": config["num_attention_heads"],
            "hkv": config["num_key_value_heads"],
            "dh": d // config["num_attention_heads"],
            "e": config["ssm_expand"] * d, "n": config["ssm_state_size"],
            "k": config["ssm_conv_width"], "r": config["ssm_dt_rank"],
            "window": config["sliding_window"],
            "vocab": config["vocab_size"], "eps": config["layer_norm_eps"],
            "kinds": list(config["layer_kinds"]),
            "published": list(config["published_layer_index"])}


def _model_config(config, traffic):
    from paddle_tpu.models import phi4flash
    s = sizes(config)
    return phi4flash.Phi4FlashConfig(
        vocab_size=s["vocab"], hidden_size=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], ff_size=s["ff"],
        ssm_inner=s["e"], ssm_state=s["n"], ssm_conv=s["k"],
        ssm_dt_rank=s["r"], window=s["window"], layer_kinds=s["kinds"],
        published_layer_index=s["published"], layer_norm_eps=s["eps"],
        initializer_range=config["initializer_range"],
        dtype=config["precision"], recompute=True)


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import phi4flash
    main, startup, _feeds, fetch = phi4flash.phi4flash_pretrain_program(
        _model_config(config, traffic), batch_rows(traffic),
        traffic["seq_len"], optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


def batch_rows(traffic):
    return traffic["global_batch"]


def tokens_per_step(traffic):
    return batch_rows(traffic) * traffic["seq_len"]


def layer_specs(config, kind):
    """{suffix: (shape, dtype, init kind)} of one layer of `kind`."""
    s = sizes(config)
    d, ff, e, n, r, dh = s["d"], s["ff"], s["e"], s["n"], s["r"], s["dh"]
    enc = config["precision"]
    out = {}
    for ln in ("ln1", "ln2"):
        out[ln + "_s"] = ((d,), "float32", "ones")
        out[ln + "_b"] = ((d,), "float32", "zeros")
    out["mlp_gate_up.w_0"] = ((d, 2 * ff), enc, "normal")
    out["mlp_down.w_0"] = ((ff, d), enc, "normal")
    if kind in MAMBA_KINDS:
        out.update({
            "in_proj.w_0": ((d, 2 * e), enc, "normal"),
            "conv.w_0": ((s["k"], e), enc, "normal"),
            "conv.b_0": ((e,), enc, "zeros"),
            "x_proj.w_0": ((e, r + 2 * n), enc, "normal"),
            "dt_proj.w_0": ((r, e), enc, "normal"),
            "dt_proj.b_0": ((e,), enc, "zeros"),
            "A_log": ((e, n), "float32", "normal"),
            "D": ((e,), "float32", "ones"),
            "out_proj.w_0": ((e, d), enc, "normal")})
    elif kind == "gmu":
        out.update({"gmu_in.w_0": ((d, e), enc, "normal"),
                    "gmu_out.w_0": ((e, d), enc, "normal")})
    else:
        width = s["hq"] * dh
        if kind == "cross":
            out["q.w_0"] = ((d, width), enc, "normal")
            out["q.b_0"] = ((width,), enc, "zeros")
        else:
            width_all = (s["hq"] + 2 * s["hkv"]) * dh
            out["qkv.w_0"] = ((d, width_all), enc, "normal")
            out["qkv.b_0"] = ((width_all,), enc, "zeros")
        for lam in ("q1", "k1", "q2", "k2"):
            out["lambda_" + lam] = ((dh,), "float32", "normal")
        out["subln_s"] = ((2 * dh,), "float32", "ones")
        out["out.w_0"] = ((width, d), enc, "normal")
        out["out.b_0"] = ((d,), enc, "zeros")
    return out


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s three kinds: normal
    of `initializer_range` (truncated at two sigma) for matrices, tables,
    Alog and the lambdas; ones for norm scales and D; zeros for biases.
    Block matrices and their biases are held in `precision`, norms, Alog,
    D, the lambdas and the table in float32."""
    s = sizes(config)
    specs = {"phi_word_embedding": ((s["vocab"], s["d"]), "float32",
                                    "normal"),
             "phi_lnf_s": ((s["d"],), "float32", "ones"),
             "phi_lnf_b": ((s["d"],), "float32", "zeros")}
    for i, kind in enumerate(s["kinds"]):
        for suffix, spec in layer_specs(config, kind).items():
            specs["phi_layer_%d_%s" % (i, suffix)] = spec
    return specs


def make_batch(config, traffic, rng):
    """One document a sequence: uniform ids from the vocabulary rows held,
    labels the tokens shifted left, every position predicted."""
    n, t = batch_rows(traffic), traffic["seq_len"]
    toks = rng.integers(0, config["vocab_size"], (n, t + 1), dtype=np.int64)
    return {"token_ids": np.ascontiguousarray(toks[:, :-1, None]),
            "labels": np.ascontiguousarray(toks[:, 1:, None]),
            "loss_mask": np.ones((n, t, 1), np.float32)}


def train_flops(config, traffic):
    return flops_hybrid.hybrid_train_flops(
        sizes(config), batch_rows(traffic), traffic["seq_len"])


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind): the program stacks the two softmaxes of every pair on the batch
    axis (2 rows a sequence) and recomputes each layer, so the forward
    kernel runs twice a layer."""
    s, t = sizes(config), traffic["seq_len"]
    calls = []
    for kind in s["kinds"]:
        if kind not in ATTN_KINDS or t * t <= 256 * 256:
            continue
        shape = {"batch": 2 * traffic["batch_per_chip"],
                 "q_heads": s["hq"] // 2, "kv_heads": s["hkv"] // 2,
                 "seq": t, "d_qk": s["dh"], "d_v": 2 * s["dh"],
                 "window": s["window"] if kind == "window" else None}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


def scan_calls(config, traffic):
    """The selective-scan calls of one step: (batch, seq, channels, state,
    forward count, backward count) a Mamba layer; recompute runs the
    forward twice."""
    s = sizes(config)
    return [(traffic["batch_per_chip"], traffic["seq_len"], s["e"], s["n"],
             2, 1) for kind in s["kinds"] if kind in MAMBA_KINDS]


# ---- the plain reference -------------------------------------------------

def block_of(batch, lo, hi):
    return {"tok": batch["token_ids"][lo:hi, :, 0].astype(np.int32),
            "lbl": batch["labels"][lo:hi, :, 0].astype(np.int32),
            "mask": batch["loss_mask"][lo:hi, :, 0]}


def _fit(n, cap):
    """Largest block <= cap that divides n, by halving (then n itself)."""
    blk = min(cap, n)
    while blk > 1 and n % blk:
        blk //= 2
    return blk if n % blk == 0 else n


def _chunked(fn, x, cap):
    """fn over the leading axis of x in blocks of <= cap rows, each under
    its own checkpoint; same values as fn(x)."""
    import jax
    rows = x.shape[0]
    blk = _fit(rows, cap)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape((rows // blk, blk) + x.shape[1:]))
    return out.reshape((rows,) + out.shape[2:])


def _mlp(u, w, mm):
    import jax
    import jax.numpy as jnp

    def block(uc):
        gate, up = jnp.split(mm(uc, w["mlp_gate_up.w_0"]), 2, axis=-1)
        return mm(jax.nn.silu(gate) * up, w["mlp_down.w_0"])

    n, t, d = u.shape
    return _chunked(block, u.reshape(n * t, d), MLP_CHUNK).reshape(n, t, d)


def _scan(xc, delta, a, b, c, d_skip, h0):
    """The selective scan as a `lax.scan` over time from state `h0`
    ((n, t, e) inputs, (n, t, N) B and C), in chunks under
    `jax.checkpoint`. Returns (final state, y)."""
    import jax
    import jax.numpy as jnp
    n, t, e = xc.shape
    chunk = _fit(t, SCAN_CHUNK)

    def by_chunk(m):
        return m.reshape(n, t // chunk, chunk, -1).transpose(1, 2, 0, 3)

    def step(h, inp):
        xt, dt, bt, ct = inp
        h = jnp.exp(dt[..., None] * a) * h \
            + (dt * xt)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], axis=-1) + d_skip * xt

    @jax.checkpoint
    def one_chunk(h, inp):
        return jax.lax.scan(step, h, inp)

    h, ys = jax.lax.scan(
        one_chunk, h0,
        (by_chunk(xc), by_chunk(delta), by_chunk(b), by_chunk(c)))
    return h, ys.reshape(t, n, e).transpose(1, 0, 2)


def _mamba(u, w, s, mm):
    """Returns (out, y): y is the scan's output before the gate. Walks the
    sequence in blocks of MLP_CHUNK steps, each under its own checkpoint,
    carrying the convolution's last K-1 inputs and the scan's state: every
    row's projections are its own, so blocking changes no value."""
    import jax
    import jax.numpy as jnp
    n, t, d = u.shape
    k, e, r, st = s["k"], s["e"], s["r"], s["n"]
    blk = _fit(t, MLP_CHUNK)
    a = -jnp.exp(w["A_log"])

    @jax.checkpoint
    def block(carry, uc):
        tail, h = carry                 # (n, K-1, e), (n, e, N)
        xs, z = jnp.split(mm(uc, w["in_proj.w_0"]), 2, axis=-1)
        padded = jnp.concatenate([tail, xs], axis=1)
        conv = sum(padded[:, i:i + blk] * w["conv.w_0"][i] for i in range(k))
        xc = jax.nn.silu(conv + w["conv.b_0"])
        dbc = mm(xc, w["x_proj.w_0"])
        dr, b, c = dbc[..., :r], dbc[..., r:r + st], dbc[..., r + st:]
        delta = jax.nn.softplus(mm(dr, w["dt_proj.w_0"])
                                + w["dt_proj.b_0"])
        h, y = _scan(xc, delta, a, b, c, w["D"], h)
        return (padded[:, blk:], h), \
            (mm(y * jax.nn.silu(z), w["out_proj.w_0"]), y)

    start = (jnp.zeros((n, k - 1, e), jnp.float32),
             jnp.zeros((n, e, st), jnp.float32))
    _end, (out, y) = jax.lax.scan(
        block, start, u.reshape(n, t // blk, blk, d).transpose(1, 0, 2, 3))

    def whole(m):
        return m.transpose(1, 0, 2, 3).reshape(n, t, m.shape[-1])

    return whole(out), whole(y)


def _gmu(u, w, memory, mm):
    import jax
    return mm(memory * jax.nn.silu(mm(u, w["gmu_in.w_0"])),
              w["gmu_out.w_0"])


def _pairs(x, heads, width):
    """(n, t, heads*width) -> (heads/2, n, 2, t, width): pair-major."""
    n, t, _ = x.shape
    return x.reshape(n, t, heads // 2, 2, width).transpose(2, 0, 3, 1, 4)


def _attention(u, w, s, lam_init, window, kv, mm):
    """Differential grouped attention; `kv` None: own K, V. Returns
    (out, (k, v)): k (groups, n, 2, t, dh), v (groups, n, t, 2 dh)."""
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    if kv is None:
        qkv = mm(u, w["qkv.w_0"]) + w["qkv.b_0"]
        q, k, v = (qkv[..., :hq * dh], qkv[..., hq * dh:(hq + hkv) * dh],
                   qkv[..., (hq + hkv) * dh:])
        kv = (_pairs(k, hkv, dh),
              v.reshape(n, t, hkv // 2, 2 * dh).transpose(2, 0, 1, 3))
    else:
        q = mm(u, w["q.w_0"]) + w["q.b_0"]
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init)
    per_group = (hq // 2) // (hkv // 2)
    k_pair = jnp.repeat(kv[0], per_group, axis=0)   # (pairs, n, 2, t, dh)
    v_pair = jnp.repeat(kv[1], per_group, axis=0)   # (pairs, n, t, 2 dh)
    bq = _fit(t, Q_BLOCK)
    q_blocks = _pairs(q, hq, dh).reshape(hq // 2, n, 2, t // bq, bq, dh)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, first_row, kj, vj):
        """One head pair's queries [first_row, first_row + bq): qb (n, 2,
        bq, dh) against kj (n, 2, t, dh), vj (n, t, 2 dh)."""
        scores = mm(qb, kj.transpose(0, 1, 3, 2)) * dh ** -0.5
        rel = (first_row + jnp.arange(bq))[:, None] - key_pos[None, :]
        seen = rel >= 0
        if window is not None:
            seen = seen & (rel < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = mm(probs, vj[:, None])                  # (n, 2, bq, 2 dh)
        return o[:, 0] - lam * o[:, 1]

    def pair(args):
        qj, kj, vj = args                           # qj (n, 2, nb, bq, dh)
        rows = jax.lax.map(
            lambda a: block(a[0], a[1], kj, vj),
            (qj.transpose(2, 0, 1, 3, 4), jnp.arange(t // bq) * bq))
        return rows.transpose(1, 0, 2, 3).reshape(n, t, 2 * dh)

    o = jax.lax.map(pair, (q_blocks, k_pair, v_pair))  # (pairs, n, t, 2dh)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + s["eps"]) * w["subln_s"] * (1.0 - lam_init)
    o = o.transpose(1, 2, 0, 3).reshape(n, t, hq * dh)
    return mm(o, w["out.w_0"]) + w["out.b_0"], kv


def lambda_init(published_index):
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def _layer(x, w, reads, kind, published_index, s, mm):
    """One layer. `reads` is what it takes from earlier layers: (M,) for
    "gmu", (K*, V*) for "cross", () else. Returns (x', makes): (M,) from
    "memory", (K*, V*) from "full", () else."""
    import jax
    u = ref.layer_norm(x, w["ln1_s"], w["ln1_b"], s["eps"])
    makes = ()
    if kind in MAMBA_KINDS:
        mix, y = jax.checkpoint(lambda u_, w_: _mamba(u_, w_, s, mm))(u, w)
        if kind == "memory":
            makes = (y,)
    elif kind == "gmu":
        mix = jax.checkpoint(lambda u_, w_, m_: _gmu(u_, w_, m_, mm))(
            u, w, reads[0])
    else:
        mix, kv = jax.checkpoint(lambda u_, w_, kv_: _attention(
            u_, w_, s, lambda_init(published_index),
            s["window"] if kind == "window" else None, kv_, mm))(
                u, w, reads if kind == "cross" else None)
        if kind == "full":
            makes = tuple(kv)
    h = x + mix
    u2 = ref.layer_norm(h, w["ln2_s"], w["ln2_b"], s["eps"])
    return h + jax.checkpoint(lambda u_, w_: _mlp(u_, w_, mm))(u2, w), makes


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    x = p["phi_word_embedding"][blk["tok"]]
    memory, kv = (), ()
    for i, kind in enumerate(s["kinds"]):
        prefix = "phi_layer_%d_" % i
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        reads = {"gmu": memory, "cross": kv}.get(kind, ())
        x, makes = jax.checkpoint(functools.partial(
            _layer, kind=kind, published_index=s["published"][i], s=s,
            mm=mm))(x, w, reads)
        if kind == "memory":
            memory = makes
        elif kind == "full":
            kv = makes
    x = ref.layer_norm(x, p["phi_lnf_s"], p["phi_lnf_b"], s["eps"])
    table = p["phi_word_embedding"]

    rows = _fit(n * t, MLP_CHUNK)
    ce = jax.lax.map(
        # (vocab, d) x (d, rows), then the small product turned: the table
        # is never transposed (a 256 MB copy at the cell's size)
        jax.checkpoint(lambda a: ref.cross_entropy(mm(table, a[0].T).T,
                                                   a[1])),
        (x.reshape(-1, rows, x.shape[-1]), blk["lbl"].reshape(-1, rows)))
    ce = ce.reshape(-1)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
