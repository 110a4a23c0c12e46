"""SmallThinker-21BA3B-Instruct causal LM training (`smallthinker`: window
and full grouped-query attention three to one, over sparse experts whose
router reads the block's input ahead of attention; arXiv:2507.20984 and the
published `modeling_smallthinker.py`): the program through paddle_tpu's
normal path, the seeded batch generator, the required-FLOPs count, the
parameter list and the plain reference.

The equations (program and reference implement exactly these; d = hidden,
D = head size, F = expert width, E = experts routed over, k = picks a token,
W = window), RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias anywhere:
    u  = RMS(h; g1)
    h1 = h + attention(u)
    x  = RMS(h1; g2)
    h' = h1 + experts(x; routed by h)
  attention   [q, k, v] = u Wqkv (Hq, Hkv, Hkv heads of D), no q/k norm;
              where `rope_layout[i]` is 1 rotary over the whole head (pairs
              (i, i + D/2), angle t * theta^(-2i/D)), else NO positions;
              causal softmax(q k^T / sqrt(D)) v, query head h reading key
              head h // (Hq / Hkv), query t seeing keys t - W + 1 .. t where
              `sliding_window_layout[i]` is 1, else every key up to t;
              out = concat Wo.
  experts     l = h Wr (float32, E logits): the router reads the layer's
              INPUT h, before its norm and before attention; picks = top-k
              of l; w = softmax over the picks' logits (and, `norm_topk_prob`,
              over their sum, which is 1); out = sum over the picks e THAT ARE
              HELD HERE (`experts_held` = (first, count)) of
              w_e W2_e(relu(W1_e x) * W3_e x): a pick on an absent expert
              adds nothing (the expert-parallel rank's share); where the
              configuration says `absent_experts` "folded", a pick on an
              absent expert e is answered by the held expert first +
              (e - first) mod count with the weight w_e, so every pick is
              answered. No shared expert, no expert bias, no auxiliary loss.
Head: logits = RMS(x; gf) Whead^T over the rows held (an untied float32
matrix); loss = mean cross-entropy over every position.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the experts are a dense masked sum (every
held expert over every token, times the token's weight for it or 0),
attention explicit scores per head under the (query, key) mask itself. It is
BLOCKED as `families/lfm2moe.py`'s (whose `_chunked`, `rms_norm` and
`rotate_half` it uses) so that at the cell's size one 16,384-token sequence
fits beside `reference.follow`'s copies of the parameters: `jax.checkpoint`
per layer and again per mixer and expert layer, token chunks for the experts
and the head, query blocks per head (28 x 16384^2 float32 scores would be
30 GB). Blocking changes no value.

Departures (the configuration file lists them): q, k, v are one matrix and
gate, up are one matrix (the same products); block matrices in bfloat16
without a float32 master copy; plain Adam; synthetic uniform tokens.
"""
import functools

from benchmark import flops_hybrid
from benchmark import reference as ref
from benchmark.families import lfm2moe as lfm

MLP_CHUNK = lfm.MLP_CHUNK   # tokens a block of the experts and the head holds
Q_BLOCK = lfm.Q_BLOCK       # queries a block of one head's scores holds
PREFIX = "st_layer_%d"


def sizes(config):
    held = config["experts_held"]
    if held[1] != config["moe_num_primary_experts"]:
        raise ValueError("moe_num_primary_experts %r is not the count "
                         "experts_held %r holds"
                         % (config["moe_num_primary_experts"], held))
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("only the softmax-over-the-picks router is built")
    index = list(config["published_layer_index"])
    return {"d": config["hidden_size"], "moe_ff": config["moe_ffn_hidden_size"],
            "hq": config["num_attention_heads"],
            "hkv": config["num_key_value_heads"], "dh": config["head_dim"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["moe_num_active_primary_experts"],
            "window": config["sliding_window_size"],
            "windowed": [config["sliding_window_layout"][i] for i in index],
            "rope": [config["rope_layout"][i] for i in index],
            "theta": config["rope_theta"], "vocab": config["vocab_size"],
            "eps": config["rms_norm_eps"],
            "norm_topk": config["norm_topk_prob"], "published": index,
            "absent": config.get("absent_experts", "nothing")}


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import smallthinker
    s = sizes(config)
    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=s["vocab"], hidden_size=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], moe_ff_size=s["moe_ff"],
        num_experts=s["routed"], top_k=s["top_k"], experts_held=s["held"],
        window=s["window"], window_layout=s["windowed"],
        rope_layout=s["rope"], rope_theta=s["theta"], norm_eps=s["eps"],
        norm_topk_prob=s["norm_topk"],
        initializer_range=config["initializer_range"],
        absent_picks=s["absent"],
        dtype=config["precision"], recompute=True)
    main, startup, _feeds, fetch = smallthinker.smallthinker_pretrain_program(
        cfg, batch_rows(traffic), traffic["seq_len"],
        optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


batch_rows = lfm.batch_rows
tokens_per_step = lfm.tokens_per_step
make_batch = lfm.make_batch
block_of = lfm.block_of


def layer_specs(config):
    """{suffix: (shape, dtype, init kind)} of a layer (all are alike)."""
    s = sizes(config)
    d, dh, enc, count = s["d"], s["dh"], config["precision"], s["held"][1]
    return {"attn_norm_s": ((d,), "float32", "ones"),
            "ffn_norm_s": ((d,), "float32", "ones"),
            "qkv.w_0": ((d, (s["hq"] + 2 * s["hkv"]) * dh), enc, "normal"),
            "out.w_0": ((s["hq"] * dh, d), enc, "normal"),
            "router.w_0": ((d, s["routed"]), "float32", "normal"),
            "experts_gate_up": ((count, d, 2 * s["moe_ff"]), enc, "normal"),
            "experts_down": ((count, s["moe_ff"], d), enc, "normal")}


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s kinds: normal of
    `initializer_range` (truncated at two sigma) for matrices, the table
    and the head, ones for norm scales. Block matrices are held in
    `precision`; norms, the router, the table and the head in float32."""
    s = sizes(config)
    specs = {"st_word_embedding": ((s["vocab"], s["d"]), "float32", "normal"),
             "st_lm_head": ((s["vocab"], s["d"]), "float32", "normal"),
             "st_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(len(s["published"])):
        for suffix, spec in layer_specs(config).items():
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


def _window_of(s, i):
    return s["window"] if s["windowed"][i] else None


def train_flops(config, traffic):
    """Per-step training FLOPs: matmul terms only, backward twice the
    forward, recomputed operations not counted, attention by the (query,
    key) pairs a query can see (T W - W (W - 1) / 2 a head under a window),
    the experts by `expected_held_rows` (static: even routing; `mfu_pct`
    leans on it)."""
    s = sizes(config)
    batch, seq = batch_rows(traffic), traffic["seq_len"]
    tokens, d, dh = batch * seq, s["d"], s["dh"]
    rows = expected_held_rows(config, traffic)
    fwd = 0
    for i in range(len(s["published"])):
        fwd += 2 * tokens * d * (s["hq"] + 2 * s["hkv"]) * dh \
            + 2 * tokens * s["hq"] * dh * d
        fwd += 2 * s["hq"] * batch * flops_hybrid.visible_area(
            seq, _window_of(s, i)) * 2 * dh
        fwd += 2 * tokens * d * s["routed"]
        fwd += 2 * rows * (d * 2 * s["moe_ff"] + s["moe_ff"] * d)
    fwd += 2 * tokens * d * s["vocab"]
    return 3 * fwd


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind) in `flops_hybrid.attention_call_flops`' form, `window` the
    layer's (None: full causal); recompute runs the forward kernel twice a
    layer."""
    s, t = sizes(config), traffic["seq_len"]
    calls = []
    for i in range(len(s["published"])):
        if t * t <= 256 * 256:
            continue
        shape = {"batch": traffic["batch_per_chip"], "q_heads": s["hq"],
                 "kv_heads": s["hkv"], "seq": t, "d_qk": s["dh"],
                 "d_v": s["dh"], "window": _window_of(s, i)}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


def gmm_calls(config, traffic):
    """The grouped-matmul calls of one step, one dict a (layer, matrix): the
    layer's name as its `moe.load` span gives it, K, N, the groups, and how
    often each kernel runs (recompute runs the forward twice)."""
    s = sizes(config)
    return [{"layer": PREFIX % i, "k": k, "n": n, "groups": s["held"][1],
             "fwd": 2, "dx": 1, "dw": 1}
            for i in range(len(s["published"]))
            for k, n in ((s["d"], 2 * s["moe_ff"]), (s["moe_ff"], s["d"]))]


# ---- the plain reference -------------------------------------------------

rms_norm = lfm.rms_norm


def route(r, w_router, s, mm):
    """(picks (tokens, k) over all experts, their weights (tokens, k)) from
    the router's own input r (tokens, d)."""
    import jax
    import jax.numpy as jnp
    logits = mm(r, w_router)
    _top, picks = jax.lax.top_k(jax.lax.stop_gradient(logits), s["top_k"])
    weights = jax.nn.softmax(jnp.take_along_axis(logits, picks, axis=1),
                             axis=-1)
    if s["norm_topk"]:
        weights = weights / jnp.sum(weights, axis=1, keepdims=True)
    return picks, weights


def expert_ffn(x, r, w_router, w13, w2, s, mm, held=None):
    """The part of the expert layer that the experts `held` = (first,
    count) give for the tokens x (tokens, d), routed by r (tokens, d); w13
    (count, d, 2F), w2 (count, F, d) are THEIR matrices. A dense masked sum:
    every held expert over every token, times the token's weight for it (0
    where it did not pick it); where absent experts are folded, a pick
    counts for the held expert congruent to it."""
    import jax
    import jax.numpy as jnp
    first, count = held or s["held"]
    picks, weights = route(r, w_router, s, mm)
    if s.get("absent") == "folded":
        picks = first + (picks - first) % count
    ids = first + jnp.arange(count)
    gates = jnp.sum(weights[:, :, None]
                    * (picks[:, :, None] == ids[None, None, :]), axis=1)

    def gated(xc, w13_e, w2_e):
        gate, up = jnp.split(mm(xc, w13_e), 2, axis=-1)
        return mm(jax.nn.relu(gate) * up, w2_e)

    def chunk(args):
        xc, gc = args

        def one(acc, e):
            w13_e, w2_e, gate_e = e
            return acc + gate_e[:, None] * gated(xc, w13_e, w2_e), None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(xc),
                              (w13, w2, gc.T))
        return acc

    return lfm._chunked(chunk, (x, gates), MLP_CHUNK)


def visible(q_pos, k_pos, window):
    """Bool (queries, keys): key s is seen by query t iff s <= t and, under
    a window, t - s < window."""
    seen = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        seen = seen & (q_pos[:, None] - k_pos[None, :] < window)
    return seen


def _attention(u, w, i, s, mm):
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    window = _window_of(s, i)
    qkv = mm(u, w["qkv.w_0"])

    def heads(m, count):
        return m.reshape(n, t, count, dh).transpose(2, 0, 1, 3)

    q = heads(qkv[..., :hq * dh], hq)               # (hq, n, t, dh)
    k = heads(qkv[..., hq * dh:(hq + hkv) * dh], hkv)
    v = heads(qkv[..., (hq + hkv) * dh:], hkv)
    if s["rope"][i]:
        q, k = lfm.rotate_half(q, s["theta"]), lfm.rotate_half(k, s["theta"])
    k, v = (jnp.repeat(m, hq // hkv, axis=0) for m in (k, v))
    bq = lfm._fit(t, Q_BLOCK)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, first_row, kh, vh):
        """One head's queries [first_row, first_row + bq): qb (n, bq, dh)
        against kh, vh (n, t, dh)."""
        scores = mm(qb, kh.transpose(0, 2, 1)) * dh ** -0.5
        seen = visible(first_row + jnp.arange(bq), key_pos, window)
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


def _layer(h, w, i, s, mm):
    import jax
    n, t, d = h.shape
    u = rms_norm(h, w["attn_norm_s"], s["eps"])
    h1 = h + jax.checkpoint(
        lambda u_, w_: _attention(u_, w_, i, s, mm))(u, w)
    x = rms_norm(h1, w["ffn_norm_s"], s["eps"])
    part = jax.checkpoint(lambda x_, r_, w_: expert_ffn(
        x_.reshape(n * t, d), r_.reshape(n * t, d), w_["router.w_0"],
        w_["experts_gate_up"], w_["experts_down"], s, mm))(x, h, w)
    return h1 + part.reshape(n, t, d)


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    x = p["st_word_embedding"][blk["tok"]]
    for i in range(len(s["published"])):
        prefix = PREFIX % i + "_"
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, i=i, s=s, mm=mm))(x, w)
    x = rms_norm(x, p["st_norm_f_s"], s["eps"])
    head = p["st_lm_head"]
    ce = lfm._chunked(
        # (vocab, d) x (d, rows), then the small product turned: the head
        # is never transposed
        lambda a: ref.cross_entropy(mm(head, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["lbl"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
