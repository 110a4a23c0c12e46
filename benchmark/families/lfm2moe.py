"""LFM2-8B-A1B causal LM training (`lfm2_moe`: gated short convolutions,
grouped-query attention with per-head q/k norms and rotary positions, sparse
experts): the program through paddle_tpu's normal path, the seeded batch
generator, the required-FLOPs count, the parameter list and the plain
reference.

The equations (program and reference implement exactly these; d = hidden,
D = head size, F = expert width, E = experts routed over, k = picks a token):
every layer is h = x + Mixer(RMS(x; g1)), y = h + FFN(RMS(h; g2)),
RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias anywhere. Mixers by
`layer_kinds`:
  conv        [B, C, u] = split3(x Win); v = B * u;
              c_t = sum_{i<K} w[i] * v_{t-(K-1)+i} (depthwise, causal, zeros
              before t = 0, no bias); out = (C * c) Wout.
  attention   [q, k, v] = x Wqkv (Hq, Hkv, Hkv heads of D); q, k <- RMS over
              each head with a learned scale of D; rotary over the whole head
              (pairs (i, i + D/2), angle t * theta^(-2i/D)); causal
              softmax(q k^T / sqrt(D)) v, query head h reading key head
              h // (Hq / Hkv); out = concat Wo.
FFN of layer i: dense where its PUBLISHED index < num_dense_layers,
W2(silu(W1 u) * W3 u) with [W1, W3] one (d, 2 ff) matrix; else experts:
s = sigmoid(u Wr) over all E; picks = top-k of s + bias (the bias a buffer
that starts at zeros, its published initial value; where the configuration
gives an `expert_bias_update_rate` the program moves it by that after each
step against every expert's load, the loss-free balance step); w =
s[picks] / (sum + 1e-6) times
`routed_scaling_factor`; out = sum over the picks e THAT ARE HELD HERE
(`experts_held` = (first, count)) of w_e W2_e(silu(W1_e u) * W3_e u): a pick
on an absent expert adds nothing (the expert-parallel rank's share).
Head: logits = RMS(x; gf) Emb^T over the rows held; loss = mean
cross-entropy over every position.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the experts are a dense masked sum (every
held expert over every token, times the token's weight for it or 0),
attention explicit scores per head. It is BLOCKED so that at the cell's size
it fits beside `reference.follow`'s copies of the parameters (24 bytes a
parameter): `jax.checkpoint` per layer and again per mixer and FFN, token
chunks for the MLPs, the experts (one expert at a time inside a chunk) and
the head, query blocks per head. Blocking changes no value.

Departures (the configuration file lists them): q, k, v are one matrix and
gate, up are one matrix (the same products); block matrices in bfloat16
without a float32 master copy; plain Adam; synthetic uniform tokens. The
reference carries no state but the parameters from step to step, so it adds
the bias's zeros on every step it follows: exact where the configuration's
`expert_bias_update_rate` is 0 (the cell's), and from the second step on a
departure of one rate a step where it is not.
"""
import functools

import numpy as np

from benchmark import reference as ref

MLP_CHUNK = 512         # tokens a block of an MLP, the experts, the head holds
Q_BLOCK = 512           # queries a block of one head's scores holds


def sizes(config):
    d = config["hidden_size"]
    held = config["experts_held"]
    if held[1] != config["num_experts"]:
        raise ValueError("num_experts %r is not the count experts_held %r "
                         "holds" % (config["num_experts"], held))
    return {"d": d, "ff": config["intermediate_size"],
            "moe_ff": config["moe_intermediate_size"],
            "hq": config["num_attention_heads"],
            "hkv": config["num_key_value_heads"],
            "dh": d // config["num_attention_heads"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["num_experts_per_tok"],
            "dense": config["num_dense_layers"],
            "k": config["conv_L_cache"], "theta": config["rope_theta"],
            "vocab": config["vocab_size"], "eps": config["norm_eps"],
            "norm_topk": config["norm_topk_prob"],
            "scaling": config["routed_scaling_factor"],
            "kinds": list(config["layer_kinds"]),
            "published": list(config["published_layer_index"])}


def _model_config(config, traffic):
    from paddle_tpu.models import lfm2moe
    s = sizes(config)
    return lfm2moe.Lfm2MoeConfig(
        vocab_size=s["vocab"], hidden_size=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], ff_size=s["ff"],
        moe_ff_size=s["moe_ff"], num_experts=s["routed"], top_k=s["top_k"],
        experts_held=s["held"], num_dense_layers=s["dense"],
        conv_width=s["k"], layer_kinds=s["kinds"],
        published_layer_index=s["published"], rope_theta=s["theta"],
        norm_eps=s["eps"], norm_topk_prob=s["norm_topk"],
        routed_scaling_factor=s["scaling"],
        expert_bias_update_rate=config.get("expert_bias_update_rate", 0.0),
        initializer_range=config["initializer_range"],
        dtype=config["precision"], recompute=True)


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import lfm2moe
    main, startup, _feeds, fetch = lfm2moe.lfm2moe_pretrain_program(
        _model_config(config, traffic), batch_rows(traffic),
        traffic["seq_len"], optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


def batch_rows(traffic):
    return traffic["global_batch"]


def tokens_per_step(traffic):
    return batch_rows(traffic) * traffic["seq_len"]


def is_dense(s, i):
    return s["published"][i] < s["dense"]


def layer_specs(config, i):
    """{suffix: (shape, dtype, init kind)} of layer i."""
    s = sizes(config)
    d, dh, enc = s["d"], s["dh"], config["precision"]
    out = {"op_norm_s": ((d,), "float32", "ones"),
           "ffn_norm_s": ((d,), "float32", "ones")}
    if s["kinds"][i] == "conv":
        out.update({"conv_in.w_0": ((d, 3 * d), enc, "normal"),
                    "conv.w_0": ((s["k"], d), enc, "normal"),
                    "conv_out.w_0": ((d, d), enc, "normal")})
    else:
        out.update({
            "qkv.w_0": ((d, (s["hq"] + 2 * s["hkv"]) * dh), enc, "normal"),
            "q_norm_s": ((dh,), "float32", "ones"),
            "k_norm_s": ((dh,), "float32", "ones"),
            "out.w_0": ((s["hq"] * dh, d), enc, "normal")})
    if is_dense(s, i):
        out.update({"mlp_gate_up.w_0": ((d, 2 * s["ff"]), enc, "normal"),
                    "mlp_down.w_0": ((s["ff"], d), enc, "normal")})
    else:
        count = s["held"][1]
        out.update({
            "router.w_0": ((d, s["routed"]), "float32", "normal"),
            "experts_gate_up": ((count, d, 2 * s["moe_ff"]), enc, "normal"),
            "experts_down": ((count, s["moe_ff"], d), enc, "normal")})
    return out


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s kinds: normal of
    `initializer_range` (truncated at two sigma) for matrices and the
    table, ones for norm scales. Block matrices and the conv weights are
    held in `precision`; norms, the router and the table in float32. The
    expert bias is no parameter (a buffer in the program that the step
    itself moves, left out here)."""
    s = sizes(config)
    specs = {"lfm_word_embedding": ((s["vocab"], s["d"]), "float32",
                                    "normal"),
             "lfm_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(len(s["kinds"])):
        for suffix, spec in layer_specs(config, i).items():
            specs["lfm_layer_%d_%s" % (i, suffix)] = spec
    return specs


def make_batch(config, traffic, rng):
    """One document a sequence: uniform ids from the vocabulary rows held,
    labels the tokens shifted left, every position predicted."""
    n, t = batch_rows(traffic), traffic["seq_len"]
    toks = rng.integers(0, config["vocab_size"], (n, t + 1), dtype=np.int64)
    return {"token_ids": np.ascontiguousarray(toks[:, :-1, None]),
            "labels": np.ascontiguousarray(toks[:, 1:, None]),
            "loss_mask": np.ones((n, t, 1), np.float32)}


def expected_held_rows(config, traffic):
    """Rows a step sends to the held experts of one layer if routing is
    even: tokens x picks x held / routed. The static counts (`train_flops`)
    use it; what a step really sent is in the `moe.load` spans."""
    s = sizes(config)
    return tokens_per_step(traffic) * s["top_k"] * s["held"][1] \
        // s["routed"]


def train_flops(config, traffic):
    """Per-step training FLOPs: matmul terms only, backward twice the
    forward, recomputed operations not counted, attention by the area a
    query can see, the experts by `expected_held_rows` (static: even
    routing; `mfu_pct` leans on it)."""
    s = sizes(config)
    batch, seq = batch_rows(traffic), traffic["seq_len"]
    tokens, d, dh = batch * seq, s["d"], s["dh"]
    rows = expected_held_rows(config, traffic)
    fwd = 0
    for i, kind in enumerate(s["kinds"]):
        if kind == "conv":
            fwd += 2 * tokens * (d * 3 * d + d * d)
        else:
            fwd += 2 * tokens * d * (s["hq"] + 2 * s["hkv"]) * dh \
                + 2 * tokens * s["hq"] * dh * d
            fwd += 2 * s["hq"] * batch * (seq * (seq + 1) // 2) * 2 * dh
        if is_dense(s, i):
            fwd += 2 * tokens * d * 2 * s["ff"] + 2 * tokens * s["ff"] * d
        else:
            fwd += 2 * tokens * d * s["routed"]
            fwd += 2 * rows * (d * 2 * s["moe_ff"] + s["moe_ff"] * d)
    fwd += 2 * tokens * d * s["vocab"]
    return 3 * fwd


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind) in `flops_hybrid.attention_call_flops`' form: plain grouped-query
    attention; recompute runs the forward kernel twice a layer."""
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
    matrix): the layer's name as its `moe.load` span gives it, K, N, the
    groups, and how often each kernel runs (recompute runs the forward
    twice)."""
    s = sizes(config)
    calls = []
    for i in range(len(s["kinds"])):
        if is_dense(s, i):
            continue
        for k, n in ((s["d"], 2 * s["moe_ff"]), (s["moe_ff"], s["d"])):
            calls.append({"layer": "lfm_layer_%d" % i, "k": k, "n": n,
                          "groups": s["held"][1], "fwd": 2, "dx": 1,
                          "dw": 1})
    return calls


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


def _chunked(fn, xs, cap):
    """fn over the leading axis of every array of the tuple `xs` in blocks
    of <= cap rows, each under its own checkpoint; same values as fn(xs)."""
    import jax
    rows = xs[0].shape[0]
    blk = _fit(rows, cap)
    out = jax.lax.map(jax.checkpoint(fn), tuple(
        x.reshape((rows // blk, blk) + x.shape[1:]) for x in xs))
    return out.reshape((rows,) + out.shape[2:])


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale


def _gated(u, w13, w2, mm):
    import jax
    import jax.numpy as jnp
    gate, up = jnp.split(mm(u, w13), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


def _dense_ffn(u, w, mm):
    n, t, d = u.shape
    return _chunked(
        lambda a: _gated(a[0], w["mlp_gate_up.w_0"], w["mlp_down.w_0"], mm),
        (u.reshape(n * t, d),), MLP_CHUNK).reshape(n, t, d)


def route(u, w_router, s, mm):
    """(picks (tokens, k) over all experts, their weights (tokens, k))."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(mm(u, w_router))
    bias = jnp.zeros((s["routed"],), jnp.float32)   # where a run starts
    _top, picks = jax.lax.top_k(jax.lax.stop_gradient(scores + bias),
                                s["top_k"])
    weights = jnp.take_along_axis(scores, picks, axis=1)
    if s["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-6)
    return picks, weights * s["scaling"]


def expert_ffn(u, w_router, w13, w2, s, mm, held=None):
    """The part of the expert layer that the experts `held` = (first,
    count) give, u (tokens, d); w13 (count, d, 2F), w2 (count, F, d) are
    THEIR matrices. A dense masked sum: every held expert over every token,
    times the token's weight for it (0 where it did not pick it)."""
    import jax
    import jax.numpy as jnp
    first, count = held or s["held"]
    picks, weights = route(u, w_router, s, mm)
    ids = first + jnp.arange(count)
    gates = jnp.sum(weights[:, :, None]
                    * (picks[:, :, None] == ids[None, None, :]), axis=1)

    def chunk(args):
        uc, gc = args

        def one(acc, e):
            w13_e, w2_e, gate_e = e
            return acc + gate_e[:, None] * _gated(uc, w13_e, w2_e, mm), None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(uc),
                              (w13, w2, gc.T))
        return acc

    return _chunked(chunk, (u, gates), MLP_CHUNK)


def _short_conv(u, w, s, mm):
    import jax.numpy as jnp
    k, t = s["k"], u.shape[1]
    b, c, x = jnp.split(mm(u, w["conv_in.w_0"]), 3, axis=-1)
    padded = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * w["conv.w_0"][i] for i in range(k))
    return mm(c * conv, w["conv_out.w_0"])


def rotate_half(x, theta):
    """x (..., t, D): pairs (i, i + D/2) turn by t * theta^(-2i/D)."""
    import jax.numpy as jnp
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(u, w, s, mm):
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    qkv = mm(u, w["qkv.w_0"])

    def heads(m, count):
        return m.reshape(n, t, count, dh).transpose(2, 0, 1, 3)

    q = heads(qkv[..., :hq * dh], hq)               # (hq, n, t, dh)
    k = heads(qkv[..., hq * dh:(hq + hkv) * dh], hkv)
    v = heads(qkv[..., (hq + hkv) * dh:], hkv)
    q = rotate_half(rms_norm(q, w["q_norm_s"], s["eps"]), s["theta"])
    k = rotate_half(rms_norm(k, w["k_norm_s"], s["eps"]), s["theta"])
    k, v = (jnp.repeat(m, hq // hkv, axis=0) for m in (k, v))
    bq = _fit(t, Q_BLOCK)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, first_row, kh, vh):
        """One head's queries [first_row, first_row + bq): qb (n, bq, dh)
        against kh, vh (n, t, dh)."""
        scores = mm(qb, kh.transpose(0, 2, 1)) * dh ** -0.5
        seen = (first_row + jnp.arange(bq))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    def head(args):
        qh, kh, vh = args
        rows = jax.lax.map(
            lambda a: block(a[0], a[1], kh, vh),
            (qh.reshape(n, t // bq, bq, dh).transpose(1, 0, 2, 3),
             jnp.arange(t // bq) * bq))
        return rows.transpose(1, 0, 2, 3).reshape(n, t, dh)

    o = jax.lax.map(head, (q, k, v))                # (hq, n, t, dh)
    return mm(o.transpose(1, 2, 0, 3).reshape(n, t, hq * dh), w["out.w_0"])


def _layer(x, w, i, s, mm):
    import jax
    u = rms_norm(x, w["op_norm_s"], s["eps"])
    mixer = _attention if s["kinds"][i] == "attention" else _short_conv
    h = x + jax.checkpoint(lambda u_, w_: mixer(u_, w_, s, mm))(u, w)
    u2 = rms_norm(h, w["ffn_norm_s"], s["eps"])
    if is_dense(s, i):
        return h + jax.checkpoint(lambda u_, w_: _dense_ffn(u_, w_, mm))(
            u2, w)
    n, t, d = u2.shape
    part = jax.checkpoint(lambda u_, w_: expert_ffn(
        u_.reshape(n * t, d), w_["router.w_0"], w_["experts_gate_up"],
        w_["experts_down"], s, mm))(u2, w)
    return h + part.reshape(n, t, d)


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    table = p["lfm_word_embedding"]
    x = table[blk["tok"]]
    for i in range(len(s["kinds"])):
        prefix = "lfm_layer_%d_" % i
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, i=i, s=s, mm=mm))(x, w)
    x = rms_norm(x, p["lfm_norm_f_s"], s["eps"])
    ce = _chunked(
        # (vocab, d) x (d, rows), then the small product turned: the table
        # is never transposed
        lambda a: ref.cross_entropy(mm(table, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["lbl"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
