"""The `nemotron_h` stack of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
causal LM training (huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-
Base-BF16: Mamba-2 layers, sparse-expert layers with non-gated relu^2
experts and a shared expert, grouped-query attention layers, each layer ONE
block alone): the program through paddle_tpu's normal path, the seeded batch
generator, the required-FLOPs count, the parameter list and the plain
reference. The model's second, denoiser tower is not built (the
configuration's file says why under `not_built`): this is the stack its
`config.json` describes, fed token ids.

The equations (program and reference implement exactly these; d = hidden):
`hybrid_override_pattern` names the layers, one letter each; with
u = RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, every layer is
y = x + block(u), no bias but the convolution's; a final norm; an untied
head.
  M   H heads of P channels (E = H P), G groups, a state of N:
      [z | xBC | dt] = u Win, widths E | E + 2 G N | H;
      xBC = silu(conv(xBC) + b): depthwise, causal, width K, zeros before
      t = 0; x (T, H, P), B, C (T, G, N) = split(xBC);
      dt_t = softplus(dt_t + dt_bias) (`time_step_limit` (0, inf): no clip);
      a_t = exp(-dt_t exp(A_log)), one scalar a head;
      S_t = a_t S_{t-1} + dt_t B_t x_t^T (S an (N, P) state a head from
      zeros; head h reads group h // (H / G)); y_t = S_t^T C_t + D_h x_t;
      y = GroupRMS(y * silu(z)): the gate first, then an RMS norm over each
      of the G runs of E / G channels, a learned scale of E; out = y Wout.
  E   s = sigmoid(u Wr) in float32 over all routed experts; picks = top-k
      of s + b (b the expert bias, zeros and unmoved; `n_group` =
      `topk_group` = 1: a plain top-k); w = s[picks] / (sum + 1e-20) times
      `routed_scaling_factor`; the sum over the picks e THAT ARE HELD HERE
      (`experts_held` = (first, count)) of w_e W2_e relu(W1_e u)^2: a pick
      on an absent expert adds nothing (`absent_experts` "nothing"), or,
      "folded", is answered by the held expert first + (e - first) mod count
      with the weight w_e; plus the shared expert W2_s relu(W1_s u)^2 at
      `moe_shared_expert_intermediate_size`, every token, unweighted.
  *   [q, k, v] = u Wqkv (Hq, Hkv, Hkv heads of D); causal
      softmax(q k^T / sqrt(D)) v, query head h reading key head
      h // (Hq / Hkv); NO rotary turn (assumed: the configuration's file
      says why); out = concat Wo.
Head: logits = RMS(x; gf) Whead^T over the rows held; loss = mean
cross-entropy over every position. No auxiliary loss.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the scan is the step-by-step recurrence above
(a `lax.scan` over t: no chunked algebra shared with the op), the experts a
dense masked sum (every held expert over every token, times the token's
weight for it or 0), attention explicit scores per head and query block. It
is BLOCKED as `families/lfm2moe.py`'s (whose `_chunked`, `_fit` and
`rms_norm` it uses; the router is `families/kimivl.py`'s) so that it fits
beside `reference.follow`'s copies of the parameters: `jax.checkpoint` per
layer, a Mamba-2 layer one GROUP at a time (its convolution, recurrence and
gated norm are a group's own) with the recurrence under a checkpoint every
SCAN_BLOCK steps, token chunks for the projections, the experts (one expert
at a time) and the head, one query head and query block at a time. Blocking
changes no value.
"""
import functools

from benchmark import flops_ssd
from benchmark import reference as ref
from benchmark.families import kimivl as kvl
from benchmark.families import lfm2moe as lfm

MLP_CHUNK = lfm.MLP_CHUNK   # tokens a block of a projection, the experts
Q_BLOCK = lfm.Q_BLOCK       # queries a block of one head's scores holds
SCAN_BLOCK = 128            # steps of the recurrence under one checkpoint
PREFIX = "nh_layer_%d"
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def sizes(config):
    held = config["experts_held"]
    if held[1] != config["n_routed_experts"]:
        raise ValueError("n_routed_experts %r is not the count experts_held "
                         "%r holds" % (config["n_routed_experts"], held))
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] \
            or set(pattern) - set(KINDS):
        raise ValueError("hybrid_override_pattern %r needs one letter of %s "
                         "a layer" % (pattern, sorted(KINDS)))
    return {"d": config["hidden_size"],
            "kinds": [KINDS[c] for c in pattern],
            "h": config["mamba_num_heads"], "p": config["mamba_head_dim"],
            "g": config["n_groups"], "n": config["ssm_state_size"],
            "k": config["conv_kernel"], "chunk": config["chunk_size"],
            "hq": config["num_attention_heads"],
            "hkv": config["num_key_value_heads"], "dh": config["head_dim"],
            "moe_ff": config["moe_intermediate_size"],
            "shared_ff": config["moe_shared_expert_intermediate_size"],
            "shared": config["n_shared_experts"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"],
            "eps": config["layer_norm_epsilon"],
            "norm_topk": config["norm_topk_prob"],
            "scaling": config["routed_scaling_factor"],
            "absent": config.get("absent_experts", "nothing")}


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import nemotron_h
    sizes(config)       # refuses a share the keys do not add up to
    cfg = nemotron_h.NemotronHConfig.from_published(
        config, dtype=config["precision"], recompute=True)
    main, startup, _feeds, fetch = nemotron_h.nemotron_h_pretrain_program(
        cfg, batch_rows(traffic), traffic["seq_len"],
        optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


batch_rows = lfm.batch_rows
tokens_per_step = lfm.tokens_per_step
make_batch = lfm.make_batch
block_of = lfm.block_of


def layer_specs(config, i):
    """{suffix: (shape, dtype, init kind)} of layer i."""
    s = sizes(config)
    d, enc, kind = s["d"], config["precision"], s["kinds"][i]
    out = {"norm_s": ((d,), "float32", "ones")}
    if kind == "mamba":
        inner, bc = s["h"] * s["p"], s["g"] * s["n"]
        out.update({
            "mamba_in_proj.w_0": ((d, 2 * inner + 2 * bc + s["h"]), enc,
                                  "normal"),
            "mamba_conv.w_0": ((s["k"], inner + 2 * bc), enc, "normal"),
            "mamba_conv.b_0": ((inner + 2 * bc,), enc, "zeros"),
            "mamba_dt_bias": ((s["h"],), "float32", "zeros"),
            "mamba_A_log": ((s["h"],), "float32", "zeros"),
            "mamba_D": ((s["h"],), "float32", "ones"),
            "mamba_norm_s": ((inner,), "float32", "ones"),
            "mamba_out_proj.w_0": ((inner, d), enc, "normal")})
    elif kind == "experts":
        count = s["held"][1]
        out.update({
            "router.w_0": ((d, s["routed"]), "float32", "normal"),
            "experts_up": ((count, d, s["moe_ff"]), enc, "normal"),
            "experts_down": ((count, s["moe_ff"], d), enc, "normal")})
        if s["shared"]:
            out.update({
                "shared_up.w_0": ((d, s["shared_ff"]), enc, "normal"),
                "shared_down.w_0": ((s["shared_ff"], d), enc, "normal")})
    else:
        width = (s["hq"] + 2 * s["hkv"]) * s["dh"]
        out.update({"attn_qkv.w_0": ((d, width), enc, "normal"),
                    "attn_out.w_0": ((s["hq"] * s["dh"], d), enc, "normal")})
    return out


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s kinds: normal of
    `initializer_range` (truncated at two sigma) for matrices, the
    convolution's taps, the table and the head; ones for norm scales and D;
    zeros for the convolution's bias, `A_log` and `dt_bias`. Block matrices
    are held in `precision`; norms, the router, the per-head scalars, the
    table and the head in float32. The expert bias is no parameter (a
    buffer of zeros in the program)."""
    s = sizes(config)
    specs = {"nh_word_embedding": ((s["vocab"], s["d"]), "float32",
                                   "normal"),
             "nh_lm_head": ((s["vocab"], s["d"]), "float32", "normal"),
             "nh_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(len(s["kinds"])):
        for suffix, spec in layer_specs(config, i).items():
            specs[(PREFIX + "_%s") % (i, suffix)] = spec
    return specs


def expected_held_rows(config, traffic):
    """Rows a step sends to the held experts of one layer: every pick where
    absent experts are folded onto them (tokens x picks, whatever the
    router does), else tokens x picks x held / routed if routing is even.
    The static counts (`train_flops`) use it; what a step really sent is in
    the `moe.load` spans."""
    s = sizes(config)
    pairs = tokens_per_step(traffic) * s["top_k"]
    return pairs if s["absent"] == "folded" \
        else pairs * s["held"][1] // s["routed"]


def train_flops(config, traffic):
    """Per-step training FLOPs at the PUBLISHED widths (an expert is 1856
    wide whatever a kernel's tile pads it to in fast memory): matmul terms
    only, backward twice the forward, recomputed operations not counted;
    attention's scores and values by the area a query can see; the experts
    by `expected_held_rows` (folded: every pick's two matmuls); the
    Mamba-2 recurrence by its recurrent form's required work
    (`flops_ssd`)."""
    s = sizes(config)
    batch, seq = batch_rows(traffic), traffic["seq_len"]
    tokens, d = batch * seq, s["d"]
    rows = expected_held_rows(config, traffic)
    plain = lambda n, width: 2 * n * (d * width + width * d)
    fwd = 0
    for kind in s["kinds"]:
        if kind == "mamba":
            inner, bc = s["h"] * s["p"], s["g"] * s["n"]
            fwd += 2 * tokens * (d * (2 * inner + 2 * bc + s["h"])
                                 + inner * d)
            fwd += flops_ssd.call_flops(batch, seq, s["h"], s["p"],
                                        s["n"])[0]
        elif kind == "experts":
            fwd += 2 * tokens * d * s["routed"] + plain(rows, s["moe_ff"])
            if s["shared"]:
                fwd += plain(tokens, s["shared_ff"])
        else:
            fwd += 2 * tokens * (d * (s["hq"] + 2 * s["hkv"]) * s["dh"]
                                 + s["hq"] * s["dh"] * d)
            fwd += 2 * s["hq"] * batch * (seq * (seq + 1) // 2) \
                * 2 * s["dh"]
    fwd += 2 * tokens * d * s["vocab"]
    return 3 * fwd


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind) in `flops_hybrid.attention_call_flops`' form: grouped-query
    attention at Hq / Hkv query heads a key head; recompute runs the
    forward kernel twice a layer."""
    s, t = sizes(config), traffic["seq_len"]
    calls = []
    for kind in s["kinds"]:
        if kind != "attention" or t * t <= 256 * 256:
            continue
        shape = {"batch": traffic["batch_per_chip"], "q_heads": s["hq"],
                 "kv_heads": s["hkv"], "seq": t, "d_qk": s["dh"],
                 "d_v": s["dh"], "window": None}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


def gmm_calls(config, traffic):
    """The grouped-matmul calls of one step, one dict a (expert layer,
    matrix): the layer's name as its `moe.load` span gives it, K, N (the
    published 1856: two matrices an expert, not three), the groups, and how
    often each kernel runs (recompute runs the forward twice)."""
    s = sizes(config)
    return [{"layer": PREFIX % i, "k": k, "n": n, "groups": s["held"][1],
             "fwd": 2, "dx": 1, "dw": 1}
            for i, kind in enumerate(s["kinds"]) if kind == "experts"
            for k, n in ((s["d"], s["moe_ff"]), (s["moe_ff"], s["d"]))]


def scan_calls(config, traffic):
    """The Mamba-2 scan calls of one step, one dict a Mamba layer in
    `flops_ssd`'s terms: the call's shape, and how often its forward and its
    backward run (recompute runs the forward twice)."""
    s = sizes(config)
    return [{"layer": PREFIX % i, "batch": traffic["batch_per_chip"],
             "seq": traffic["seq_len"], "heads": s["h"], "head_dim": s["p"],
             "groups": s["g"], "state": s["n"], "fwd": 2, "bwd": 1}
            for i, kind in enumerate(s["kinds"]) if kind == "mamba"]


# ---- the plain reference -------------------------------------------------

rms_norm = lfm.rms_norm


def _by_tokens(fn, u):
    """fn over the tokens of u (n, t, d) in chunks of MLP_CHUNK."""
    n, t, d = u.shape
    out = lfm._chunked(lambda a: fn(a[0]), (u.reshape(n * t, d),), MLP_CHUNK)
    return out.reshape(n, t, -1)


def _relu2_mlp(u, w1, w2, mm):
    import jax
    import jax.numpy as jnp
    return mm(jnp.square(jax.nn.relu(mm(u, w1))), w2)


def recurrence(x, dt, a, b, c):
    """y_t = S_t^T C_t over S_t = a_t S_{t-1} + dt_t B_t x_t^T, step by
    step, for the heads of ONE group: x (n, t, H', P), dt, a (n, t, H'),
    b, c (n, t, N), which all H' heads read; S (N, P) a head, from zeros;
    SCAN_BLOCK steps under one checkpoint."""
    import jax
    import jax.numpy as jnp
    n, t, h, p = x.shape
    blk = lfm._fit(t, SCAN_BLOCK)

    def step(state, now):
        x_t, dt_t, a_t, b_t, c_t = now
        wrote = (dt_t[..., None, None] * b_t[:, None, :, None]) \
            * x_t[..., None, :]
        state = a_t[..., None, None] * state + wrote
        return state, jnp.sum(state * c_t[:, None, :, None], axis=-2)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    def cut(m):     # (n, t, ...) -> (t / blk, blk, n, ...)
        m = jnp.moveaxis(m, 1, 0)
        return m.reshape((t // blk, blk) + m.shape[1:])

    _last, ys = jax.lax.scan(block, jnp.zeros((n, h, b.shape[-1], p),
                                              x.dtype),
                             tuple(cut(m) for m in (x, dt, a, b, c)))
    return jnp.moveaxis(ys.reshape((t,) + ys.shape[2:]), 0, 1)


def mamba(u, w, s, mm):
    """The Mamba-2 mixer over u (n, t, d). Between its two projections
    (token chunks) everything is a group's own business: the depthwise
    convolution, the recurrence (a group's heads read its B and C) and the
    gated norm (over a group's E / G channels). So the groups are walked
    one at a time, each under a checkpoint: a `lax.map` over the groups'
    slices of the projection and of the per-channel and per-head
    parameters."""
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    h, p, g, st, k = s["h"], s["p"], s["g"], s["n"], s["k"]
    inner, bc, hg = h * p, g * st, h // g
    proj = _by_tokens(lambda a: mm(a, w["mamba_in_proj.w_0"]), u)

    def groups(m, first, width):
        """Columns first .. first + G width of the last axis -> (G, ...,
        width): group j's run."""
        m = m[..., first:first + g * width]
        return jnp.moveaxis(m.reshape(m.shape[:-1] + (g, width)), -2, 0)

    def conv_silu(m, taps, bias):
        padded = jnp.pad(m, ((0, 0), (k - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + t] * taps[i]
                               for i in range(k)) + bias)

    @jax.checkpoint
    def one(a):
        z, x, b, c, dt = a["z"], a["x"], a["b"], a["c"], a["dt"]
        x = conv_silu(x, a["tx"], a["bx"]).reshape(n, t, hg, p)
        b, c = conv_silu(b, a["tb"], a["bb"]), conv_silu(c, a["tc"], a["bc"])
        dt = jax.nn.softplus(dt + a["dt_bias"])
        decay = jnp.exp(-dt * jnp.exp(a["a_log"]))
        y = recurrence(x, dt, decay, b, c) + a["skip"][:, None] * x
        y = y.reshape(n, t, hg * p) * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + s["eps"])
        return y * a["scale"]

    taps, bias = w["mamba_conv.w_0"], w["mamba_conv.b_0"]
    y = jax.lax.map(one, {
        "z": groups(proj, 0, hg * p), "x": groups(proj, inner, hg * p),
        "b": groups(proj, 2 * inner, st),
        "c": groups(proj, 2 * inner + bc, st),
        "dt": groups(proj, 2 * inner + 2 * bc, hg),
        "tx": groups(taps, 0, hg * p), "bx": groups(bias, 0, hg * p),
        "tb": groups(taps, inner, st), "bb": groups(bias, inner, st),
        "tc": groups(taps, inner + bc, st),
        "bc": groups(bias, inner + bc, st),
        "dt_bias": w["mamba_dt_bias"].reshape(g, hg),
        "a_log": w["mamba_A_log"].reshape(g, hg),
        "skip": w["mamba_D"].reshape(g, hg),
        "scale": w["mamba_norm_s"].reshape(g, hg * p)})
    y = jnp.moveaxis(y, 0, -2).reshape(n, t, inner)
    return _by_tokens(lambda a: mm(a, w["mamba_out_proj.w_0"]), y)


route = kvl.route       # sigmoid, a plain top-k, over (sum + 1e-20)


def expert_ffn(u, w_router, w1, w2, s, mm, held=None):
    """The part of the expert layer that the experts `held` = (first,
    count) give, u (tokens, d); w1 (count, d, F), w2 (count, F, d) are
    THEIR matrices. A dense masked sum: every held expert over every token,
    times the token's weight for it (0 where it did not pick it); where
    absent experts are folded, a pick counts for the held expert congruent
    to it."""
    import jax
    import jax.numpy as jnp
    first, count = held or s["held"]
    picks, weights = route(u, w_router, s, mm)
    if s.get("absent") == "folded":
        picks = first + (picks - first) % count
    ids = first + jnp.arange(count)
    gates = jnp.sum(weights[:, :, None]
                    * (picks[:, :, None] == ids[None, None, :]), axis=1)

    @jax.checkpoint
    def one(acc, e):
        """One held expert over every token, in token chunks."""
        w1_e, w2_e, gate_e = e
        part = lfm._chunked(
            lambda a: a[1][:, None] * _relu2_mlp(a[0], w1_e, w2_e, mm),
            (u, gate_e), MLP_CHUNK)
        return acc + part, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (w1, w2, gates.T))
    return acc


def ffn_part(u, w, s, mm, held=None, shared=True):
    """The expert layer's part for the experts `held` (default: the
    configuration's), with the shared expert where `shared`."""
    n, t, d = u.shape
    out = expert_ffn(u.reshape(n * t, d), w["router.w_0"], w["experts_up"],
                     w["experts_down"], s, mm, held=held).reshape(n, t, d)
    if shared and s["shared"]:
        out = out + _by_tokens(lambda a: _relu2_mlp(
            a, w["shared_up.w_0"], w["shared_down.w_0"], mm), u)
    return out


def attention(u, w, s, mm):
    """Grouped-query attention over u (n, t, d), ONE QUERY HEAD AT A TIME (a
    scan over the heads' slices of Wq and Wo under a checkpoint a head),
    query blocks inside it."""
    import jax
    import jax.numpy as jnp
    n, t, d = u.shape
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    wq, wk, wv = jnp.split(w["attn_qkv.w_0"],
                           [hq * dh, (hq + hkv) * dh], axis=1)
    k = mm(u, wk).reshape(n, t, hkv, dh)
    v = mm(u, wv).reshape(n, t, hkv, dh)
    bq = lfm._fit(t, Q_BLOCK)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, first_row, kh, vh):
        scores = mm(qb, kh.transpose(0, 2, 1)) * dh ** -0.5
        seen = (first_row + jnp.arange(bq))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    @jax.checkpoint
    def head(out, slices):
        wq_h, wo_h, index = slices      # (d, D), (D, d), the head's number
        q = mm(u, wq_h)
        kv_head = index // (hq // hkv)
        kh = jnp.take(k, kv_head, axis=2)
        vh = jnp.take(v, kv_head, axis=2)
        rows = jax.lax.map(
            lambda a: block(a[0], a[1], kh, vh),
            (q.reshape(n, t // bq, bq, dh).transpose(1, 0, 2, 3),
             jnp.arange(t // bq) * bq))
        o = rows.transpose(1, 0, 2, 3).reshape(n, t, dh)
        return out + mm(o, wo_h), None

    out, _ = jax.lax.scan(head, jnp.zeros((n, t, d), u.dtype), (
        wq.reshape(d, hq, dh).transpose(1, 0, 2),
        w["attn_out.w_0"].reshape(hq, dh, d), jnp.arange(hq)))
    return out


def block_part(u, w, kind, s, mm):
    if kind == "mamba":
        return mamba(u, w, s, mm)
    if kind == "experts":
        return ffn_part(u, w, s, mm)
    return attention(u, w, s, mm)


def _layer(x, w, kind, s, mm):
    return x + block_part(rms_norm(x, w["norm_s"], s["eps"]), w, kind, s, mm)


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    x = p["nh_word_embedding"][blk["tok"]]
    for i, kind in enumerate(s["kinds"]):
        prefix = PREFIX % i + "_"
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, kind=kind, s=s,
                                             mm=mm))(x, w)
    x = rms_norm(x, p["nh_norm_f_s"], s["eps"])
    head = p["nh_lm_head"]
    ce = lfm._chunked(
        # (vocab, d) x (d, rows), then the small product turned: the head
        # is never transposed
        lambda a: ref.cross_entropy(mm(head, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["lbl"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
