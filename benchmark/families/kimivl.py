"""Kimi-VL-A3B-Instruct's language model, causal LM training (`kimi_vl`: the
text decoder of huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, DeepSeek-V3
shaped: latent attention with its decoupled rotary part in EVERY layer, over
a 64-wide sigmoid router with top-6 and two shared experts): the program
through paddle_tpu's normal path, the seeded batch generator, the
required-FLOPs count, the parameter list and the plain reference. The
vision tower and its projector are not built (the configuration's file says
why under `not_built`): the model is fed token ids alone.

The equations (program and reference implement exactly these; d = hidden,
H = heads, E = experts routed over, k = picks a token): every layer is
h = x + MLA(RMS(x; g1)), y = h + FFN(RMS(h; g2)),
RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias anywhere; a final norm;
an untied head. With u the normed input:
  MLA   q = u Wq -> (H, 128 + 64); [c | k_pe] = u Wkva -> (512 | 64);
        [k_nope | v] = RMS(c; scale of 512) Wkvb -> (H, 128 | 128);
        rotary, position t = 0..T-1, on the last 64 numbers of every query
        head and on k_pe (ONE vector a token): for i = 0..31,
        phi = t * theta^(-2i/64),
        (x_2i, x_2i+1) <- (x_2i cos phi - x_2i+1 sin phi,
                           x_2i sin phi + x_2i+1 cos phi);
        no scaling (`rope_scaling` null);
        k = [k_nope | k_pe, the same for every head]; causal
        softmax(q k^T / sqrt(192)) v; out = concat Wo.
  FFN   of layer i: dense where its PUBLISHED index (from 0) <
        `first_k_dense_replace`, Wd(silu(Wg u) * Wu u) with [Wg, Wu] one
        (d, 2 ff) matrix; else shared(u) + experts(u): shared the same
        gated MLP at `n_shared_experts` x the experts' width (two shared
        experts side by side: the same products), every token, unweighted;
        experts: s = sigmoid(u Wr) in float32 over all E; picks = top-k of
        s + b (b the expert bias, zeros and unmoved; `n_group` =
        `topk_group` = 1: a plain top-k); w = s[picks] / (sum + 1e-20)
        times `routed_scaling_factor`; the sum over the picks e THAT ARE
        HELD HERE (`experts_held` = (first, count)) of
        w_e W2_e(silu(W1_e u) * W3_e u): a pick on an absent expert adds
        nothing (`absent_experts` "nothing"), or, "folded", is answered by
        the held expert first + (e - first) mod count with the weight w_e,
        so every pick is answered.
Head: logits = RMS(x; gf) Whead^T over the rows held; loss = mean
cross-entropy over every position. No auxiliary loss.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the rotation on explicit (2i, 2i+1) pairs,
attention explicit scores per head and query block, the experts a dense
masked sum (every held expert over every token, times the token's weight
for it or 0). It is BLOCKED as `families/lfm2moe.py`'s (whose `_chunked`,
`_gated` and `rms_norm` it uses) so that it fits beside
`reference.follow`'s copies of the parameters: `jax.checkpoint` per layer
and again per mixer and FFN, token chunks for the MLPs, the experts and the
head, query blocks per head. Blocking changes no value.
"""
import functools

from benchmark import reference as ref
from benchmark.families import lfm2moe as lfm

MLP_CHUNK = lfm.MLP_CHUNK   # tokens a block of an MLP, the experts, the head
Q_BLOCK = lfm.Q_BLOCK       # queries a block of one head's scores holds
PREFIX = "kvl_layer_%d"
ROUTE_EPS = 1e-20


def sizes(config):
    held = config["experts_held"]
    if held[1] != config["n_routed_experts"]:
        raise ValueError("n_routed_experts %r is not the count experts_held "
                         "%r holds" % (config["n_routed_experts"], held))
    index = list(config.get("published_layer_index")
                 or range(config["num_hidden_layers"]))
    if len(index) != config["num_hidden_layers"]:
        raise ValueError("published_layer_index needs one entry a layer")
    return {"d": config["hidden_size"], "ff": config["intermediate_size"],
            "moe_ff": config["moe_intermediate_size"],
            "h": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "kv_rank": config["kv_lora_rank"],
            "theta": config["rope_theta"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "dense": config["first_k_dense_replace"],
            "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
            "norm_topk": config["norm_topk_prob"],
            "scaling": config["routed_scaling_factor"],
            "published": index,
            "absent": config.get("absent_experts", "nothing")}


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import kimi_vl
    sizes(config)       # refuses a share the keys do not add up to
    cfg = kimi_vl.KimiVLConfig.from_published(
        config, dtype=config["precision"], recompute=True)
    main, startup, _feeds, fetch = kimi_vl.kimi_vl_pretrain_program(
        cfg, batch_rows(traffic), traffic["seq_len"],
        optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


batch_rows = lfm.batch_rows
tokens_per_step = lfm.tokens_per_step
make_batch = lfm.make_batch
block_of = lfm.block_of


def is_dense(s, i):
    return s["published"][i] < s["dense"]


def layer_specs(config, i):
    """{suffix: (shape, dtype, init kind)} of layer i."""
    s = sizes(config)
    d, enc, h = s["d"], config["precision"], s["h"]
    out = {"attn_norm_s": ((d,), "float32", "ones"),
           "ffn_norm_s": ((d,), "float32", "ones"),
           "mla_q.w_0": ((d, h * (s["nope"] + s["rope"])), enc, "normal"),
           "mla_kv_a.w_0": ((d, s["kv_rank"] + s["rope"]), enc, "normal"),
           "mla_kv_a_norm_s": ((s["kv_rank"],), "float32", "ones"),
           "mla_kv_b.w_0": ((s["kv_rank"], h * (s["nope"] + s["dv"])), enc,
                            "normal"),
           "mla_out.w_0": ((h * s["dv"], d), enc, "normal")}
    if is_dense(s, i):
        out.update({"mlp_gate_up.w_0": ((d, 2 * s["ff"]), enc, "normal"),
                    "mlp_down.w_0": ((s["ff"], d), enc, "normal")})
    else:
        count, wide = s["held"][1], s["shared"] * s["moe_ff"]
        out.update({
            "router.w_0": ((d, s["routed"]), "float32", "normal"),
            "experts_gate_up": ((count, d, 2 * s["moe_ff"]), enc, "normal"),
            "experts_down": ((count, s["moe_ff"], d), enc, "normal"),
            "shared_gate_up.w_0": ((d, 2 * wide), enc, "normal"),
            "shared_down.w_0": ((wide, d), enc, "normal")})
    return out


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s kinds: normal of
    `initializer_range` (truncated at two sigma) for matrices, the table
    and the head, ones for norm scales. Block matrices are held in
    `precision`; norms, the router, the table and the head in float32. The
    expert bias is no parameter (a buffer of zeros in the program)."""
    s = sizes(config)
    specs = {"kvl_word_embedding": ((s["vocab"], s["d"]), "float32",
                                    "normal"),
             "kvl_lm_head": ((s["vocab"], s["d"]), "float32", "normal"),
             "kvl_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(len(s["published"])):
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
    """Per-step training FLOPs: matmul terms only, backward twice the
    forward, recomputed operations not counted; attention's scores and
    values by the area a query can see (T (T + 1) / 2 a head, at 192 and
    128); the experts by `expected_held_rows` (folded: every pick's three
    matmuls)."""
    s = sizes(config)
    batch, seq = batch_rows(traffic), traffic["seq_len"]
    tokens, d, h = batch * seq, s["d"], s["h"]
    rows = expected_held_rows(config, traffic)
    d_qk = s["nope"] + s["rope"]
    gated = lambda n, width: 2 * n * (d * 2 * width + width * d)
    fwd = 0
    for i in range(len(s["published"])):
        fwd += 2 * tokens * (d * h * d_qk + d * (s["kv_rank"] + s["rope"])
                             + s["kv_rank"] * h * (s["nope"] + s["dv"])
                             + h * s["dv"] * d)
        fwd += 2 * h * batch * (seq * (seq + 1) // 2) * (d_qk + s["dv"])
        if is_dense(s, i):
            fwd += gated(tokens, s["ff"])
        else:
            fwd += 2 * tokens * d * s["routed"] + gated(rows, s["moe_ff"]) \
                + gated(tokens, s["shared"] * s["moe_ff"])
    fwd += 2 * tokens * d * s["vocab"]
    return 3 * fwd


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind) in `flops_hybrid.attention_call_flops`' form: MLA after its
    latent is decompressed, one query head a key/value head, D 192 and
    Dv 128; recompute runs the forward kernel twice a layer."""
    s, t = sizes(config), traffic["seq_len"]
    calls = []
    for _i in range(len(s["published"])):
        if t * t <= 256 * 256:
            continue
        shape = {"batch": traffic["batch_per_chip"], "q_heads": s["h"],
                 "kv_heads": s["h"], "seq": t,
                 "d_qk": s["nope"] + s["rope"], "d_v": s["dv"],
                 "window": None}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


def gmm_calls(config, traffic):
    """The grouped-matmul calls of one step, one dict a (expert layer,
    matrix): the layer's name as its `moe.load` span gives it, K, N, the
    groups, and how often each kernel runs (recompute runs the forward
    twice)."""
    s = sizes(config)
    return [{"layer": PREFIX % i, "k": k, "n": n, "groups": s["held"][1],
             "fwd": 2, "dx": 1, "dw": 1}
            for i in range(len(s["published"])) if not is_dense(s, i)
            for k, n in ((s["d"], 2 * s["moe_ff"]), (s["moe_ff"], s["d"]))]


# ---- the plain reference -------------------------------------------------

rms_norm = lfm.rms_norm


def rotate_pairs(x, theta):
    """x (..., t, R), position t at row t: the pair (x_2i, x_2i+1) turns by
    phi = t * theta^(-2i/R)."""
    import jax.numpy as jnp
    t, r = x.shape[-2], x.shape[-1]
    i = jnp.arange(r // 2, dtype=jnp.float32)
    phi = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * (theta ** (-2.0 * i / r))[None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(phi) - b * jnp.sin(phi),
                      a * jnp.sin(phi) + b * jnp.cos(phi)],
                     axis=-1).reshape(x.shape)


def _gated_mlp(u, w13, w2, mm):
    n, t, d = u.shape
    return lfm._chunked(lambda a: lfm._gated(a[0], w13, w2, mm),
                        (u.reshape(n * t, d),), MLP_CHUNK).reshape(n, t, d)


def mla(u, w, s, mm, positions=True):
    """Latent attention over u (n, t, d) for the heads whose columns and
    rows `w` holds, ONE HEAD AT A TIME (a scan over the heads' slices of
    Wq, Wkvb and Wo under a checkpoint a head, so that no array of all
    heads' queries, keys or scores is held); `positions=False` leaves the
    rotary part unturned (the no-position form, for the test of what
    positions change)."""
    import jax
    import jax.numpy as jnp
    n, t, d = u.shape
    nope, rope, dv = s["nope"], s["rope"], s["dv"]
    d_qk = nope + rope
    h = w["mla_q.w_0"].shape[1] // d_qk
    latent, k_pe = jnp.split(mm(u, w["mla_kv_a.w_0"]), [s["kv_rank"]],
                             axis=-1)
    if positions:
        k_pe = rotate_pairs(k_pe, s["theta"])   # once, for every head
    c = rms_norm(latent, w["mla_kv_a_norm_s"], s["eps"])
    bq = lfm._fit(t, Q_BLOCK)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, first_row, kh, vh):
        """One head's queries [first_row, first_row + bq): qb (n, bq, D)
        against kh (n, t, D), vh (n, t, Dv)."""
        scores = mm(qb, kh.transpose(0, 2, 1)) * d_qk ** -0.5
        seen = (first_row + jnp.arange(bq))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    @jax.checkpoint
    def head(out, slices):
        wq, wkvb, wo = slices       # (d, D), (rank, nope + dv), (dv, d)
        q = mm(u, wq)
        if positions:
            q = jnp.concatenate(
                [q[..., :nope], rotate_pairs(q[..., nope:], s["theta"])],
                axis=-1)
        kv = mm(c, wkvb)
        kh = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        rows = jax.lax.map(
            lambda a: block(a[0], a[1], kh, kv[..., nope:]),
            (q.reshape(n, t // bq, bq, d_qk).transpose(1, 0, 2, 3),
             jnp.arange(t // bq) * bq))
        o = rows.transpose(1, 0, 2, 3).reshape(n, t, dv)
        return out + mm(o, wo), None

    def by_head(m, width):      # (rows, h * width) -> (h, rows, width)
        return m.reshape(m.shape[0], h, width).transpose(1, 0, 2)

    out, _ = jax.lax.scan(head, jnp.zeros((n, t, d), u.dtype), (
        by_head(w["mla_q.w_0"], d_qk), by_head(w["mla_kv_b.w_0"], nope + dv),
        w["mla_out.w_0"].reshape(h, dv, d)))
    return out


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
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True)
                             + ROUTE_EPS)
    return picks, weights * s["scaling"]


def expert_ffn(u, w_router, w13, w2, s, mm, held=None):
    """The part of the expert layer that the experts `held` = (first,
    count) give, u (tokens, d); w13 (count, d, 2F), w2 (count, F, d) are
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
        w13_e, w2_e, gate_e = e
        part = lfm._chunked(
            lambda a: a[1][:, None] * lfm._gated(a[0], w13_e, w2_e, mm),
            (u, gate_e), MLP_CHUNK)
        return acc + part, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (w13, w2, gates.T))
    return acc


def ffn_part(u, w, s, mm, held=None, shared=True):
    """The expert layer's part for the experts `held` (default: the
    configuration's), with the shared expert where `shared`."""
    n, t, d = u.shape
    out = expert_ffn(u.reshape(n * t, d), w["router.w_0"],
                     w["experts_gate_up"], w["experts_down"], s, mm,
                     held=held).reshape(n, t, d)
    if shared and s["shared"]:
        out = out + _gated_mlp(u, w["shared_gate_up.w_0"],
                               w["shared_down.w_0"], mm)
    return out


def _layer(x, w, i, s, mm):
    import jax
    u = rms_norm(x, w["attn_norm_s"], s["eps"])
    h = x + jax.checkpoint(lambda u_, w_: mla(u_, w_, s, mm))(u, w)
    u2 = rms_norm(h, w["ffn_norm_s"], s["eps"])
    if is_dense(s, i):
        return h + jax.checkpoint(lambda u_, w_: _gated_mlp(
            u_, w_["mlp_gate_up.w_0"], w_["mlp_down.w_0"], mm))(u2, w)
    return h + jax.checkpoint(lambda u_, w_: ffn_part(u_, w_, s, mm))(u2, w)


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    x = p["kvl_word_embedding"][blk["tok"]]
    for i in range(len(s["published"])):
        prefix = PREFIX % i + "_"
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, i=i, s=s, mm=mm))(x, w)
    x = rms_norm(x, p["kvl_norm_f_s"], s["eps"])
    head = p["kvl_lm_head"]
    ce = lfm._chunked(
        # (vocab, d) x (d, rows), then the small product turned: the head
        # is never transposed
        lambda a: ref.cross_entropy(mm(head, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["lbl"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
