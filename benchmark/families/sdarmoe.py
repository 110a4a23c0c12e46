"""SDAR-30B-A3B-Chat trained as a block-diffusion model (`sdar_moe`; SDAR,
arXiv:2510.06303, whose training form is the block-diffusion objective of
BD3-LMs, arXiv:2503.09573): the program through paddle_tpu's normal path,
the seeded batch generator (tokens, noise levels, masks, loss weights), the
required-FLOPs count, the parameter list and the plain reference.

The equations (program and reference implement exactly these; d = hidden,
D = head size, F = expert width, E = experts routed over, k = picks a token,
L = block length). A document x of T tokens is cut into T/L blocks. The
host draws for block b a level t_b ~ U(`noise_range`) and masks each of the
block's tokens independently with probability t_b: noisy_i = MASK where
m_i = 1, else x_i. The model sees the 2T rows z = [noisy | clean], embeds
them with one table and runs the layers ONCE over all 2T rows. Row r has
position p(r) = r mod T and block b(r) = p(r) // L. Every layer,
RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias anywhere:
    u  = RMS(h; g1);  [q, k, v] = u Wqkv  (Hq, Hkv, Hkv heads of D)
    q, k <- RMS over each head's D numbers (learned scales gq, gk), then
            rotary at p(r): pairs (i, i + D/2), angle p * theta^(-2i/D)
    o  = softmax(q k^T / sqrt(D) + mask) v, query head j reading key head
         j // (Hq / Hkv)
    h1 = h + o Wo;  x' = RMS(h1; g2)
    l  = x' Wr (float32, E logits); picks = top-k of l; w = softmax over the
         picks' logits (`norm_topk_prob` then divides by their sum, 1)
    h' = h1 + sum over the picks e of w_e W2_e(silu(W1_e x') * W3_e x')
  mask: query row r sees key row s iff
    r clean, s clean, b(s) <= b(r)      (block-causal)
    r noisy, s noisy, b(s) == b(r)      (its own block, both directions)
    r noisy, s clean, b(s) <  b(r)      (the clean past, strictly)
    (a clean query never sees a noisy key)
  experts: the sum runs over the picks THAT ARE HELD HERE (`experts_held` =
    (first, count)); where the configuration says `absent_experts`
    "folded", a pick on an absent expert e is answered by the held expert
    first + (e - first) mod count with the weight w_e, so every pick is
    answered (the expert-parallel rank's load when every rank brings such a
    batch).
Head and loss over the noisy half's T rows only: logits = RMS(h_r; gf)
Whead^T over the vocabulary rows held; loss = sum_i weight_i CE(logits_i,
x_i), weight_i = m_i / t_b(i) / (B T) made on the host: the prediction is of
the token AT the masked position (no shift). Visible pairs a sequence and
head: T (T + L) / 2 + T (T - L) / 2 + T L = T^2 + T L of the 4 T^2.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels: the boolean mask from the rule above per
block of query rows (`visible`), attention explicit scores per head and
query block, the experts a dense masked sum. It is BLOCKED as
`families/lfm2moe.py`'s (whose `_chunked`, `_gated`, `rms_norm` and
`rotate_half` it uses; `rotate_half` turns by a row's index on its own axis,
so each half of T rows is turned apart) and walks attention ONE KEY HEAD
WITH ITS GROUP OF QUERY HEADS at a time (as `families/kimivl.py` walks a
head), so that it fits beside `reference.follow`'s copies of the parameters:
with every head's q, k, v and o held at once a block's temporaries were
9.1 GiB at the cell's size, so 2.2. Blocking changes no value.
"""
import functools

import numpy as np

from benchmark import flops_bd
from benchmark import reference as ref
from benchmark.families import lfm2moe as lfm
from benchmark.families import smallthinker

MLP_CHUNK = lfm.MLP_CHUNK   # tokens a block of the experts and the head holds
Q_BLOCK = lfm.Q_BLOCK       # queries a block of one head's scores holds
PREFIX = "sdar_layer_%d"


def sizes(config, traffic):
    held = config["experts_held"]
    if held[1] != config["num_experts"]:
        raise ValueError("num_experts %r is not the count experts_held %r "
                         "holds" % (config["num_experts"], held))
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 \
            or config["hidden_act"] != "silu" or config["rope_scaling"] \
            or config["use_sliding_window"]:
        raise ValueError("sdar_moe builds expert layers alone, silu gates, "
                         "plain rotary positions and no window")
    length, t = int(traffic["block_length"]), int(traffic["seq_len"])
    if t % length:
        raise ValueError("blocks of %d do not divide %d tokens"
                         % (length, t))
    return {"d": config["hidden_size"],
            "moe_ff": config["moe_intermediate_size"],
            "hq": config["num_attention_heads"],
            "hkv": config["num_key_value_heads"], "dh": config["head_dim"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["num_experts_per_tok"],
            "layers": config["num_hidden_layers"],
            "theta": config["rope_theta"], "vocab": config["vocab_size"],
            "mask_id": config["mask_token_id"],
            "eps": config["rms_norm_eps"],
            "norm_topk": config["norm_topk_prob"],
            "absent": config.get("absent_experts", "nothing"),
            "length": length, "t": t}


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import sdar_moe
    s = sizes(config, traffic)
    cfg = sdar_moe.SdarMoeConfig(
        vocab_size=s["vocab"], hidden_size=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], moe_ff_size=s["moe_ff"],
        num_experts=s["routed"], top_k=s["top_k"], num_layers=s["layers"],
        experts_held=s["held"], absent_picks=s["absent"],
        block_length=s["length"], mask_token_id=s["mask_id"],
        rope_theta=s["theta"], norm_eps=s["eps"],
        norm_topk_prob=s["norm_topk"],
        initializer_range=config["initializer_range"],
        dtype=config["precision"], recompute=True)
    main, startup, _feeds, fetch = sdar_moe.sdar_pretrain_program(
        cfg, traffic["seq_len"], optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


batch_rows = lfm.batch_rows


def tokens_per_step(traffic):
    """The documents' tokens a step: what a user trains on, not the 2T rows
    the model runs for them."""
    return batch_rows(traffic) * traffic["seq_len"]


def model_rows(traffic):
    """The rows a step's layers run over: a noisy and a clean copy."""
    return 2 * tokens_per_step(traffic)


def layer_specs(config, traffic):
    """{suffix: (shape, dtype, init kind)} of a layer (all are alike)."""
    s = sizes(config, traffic)
    d, dh, enc, count = s["d"], s["dh"], config["precision"], s["held"][1]
    return {"attn_norm_s": ((d,), "float32", "ones"),
            "ffn_norm_s": ((d,), "float32", "ones"),
            "qkv.w_0": ((d, (s["hq"] + 2 * s["hkv"]) * dh), enc, "normal"),
            "q_norm_s": ((dh,), "float32", "ones"),
            "k_norm_s": ((dh,), "float32", "ones"),
            "out.w_0": ((s["hq"] * dh, d), enc, "normal"),
            "router.w_0": ((d, s["routed"]), "float32", "normal"),
            "experts_gate_up": ((count, d, 2 * s["moe_ff"]), enc, "normal"),
            "experts_down": ((count, s["moe_ff"], d), enc, "normal")}


def param_specs(config, traffic):
    """The seeded weights, in `benchmark/weights.py`'s kinds: normal of
    `initializer_range` (truncated at two sigma) for matrices, the table
    and the head, ones for norm scales. Block matrices are held in
    `precision`; norms, the router, the table and the head in float32."""
    s = sizes(config, traffic)
    specs = {"sdar_word_embedding": ((s["vocab"], s["d"]), "float32",
                                     "normal"),
             "sdar_lm_head": ((s["vocab"], s["d"]), "float32", "normal"),
             "sdar_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(s["layers"]):
        for suffix, spec in layer_specs(config, traffic).items():
            specs[(PREFIX + "_%s") % (i, suffix)] = spec
    return specs


def make_batch(config, traffic, rng):
    """One document a sequence: uniform ids from the held rows below MASK
    (the last row held), a noise level a block from U(`noise_range`), each
    token masked with its block's level, and the loss weights
    m / t / (B T): the sum over a batch's row blocks of the weighted
    cross-entropies is the step's loss."""
    n, t, length = batch_rows(traffic), traffic["seq_len"], \
        int(traffic["block_length"])
    lo, hi = traffic["noise_range"]
    mask_id = config["mask_token_id"]
    toks = rng.integers(0, mask_id, (n, t), dtype=np.int64)
    level = np.repeat(rng.uniform(lo, hi, (n, t // length)), length, axis=1)
    masked = rng.uniform(size=(n, t)) < level
    weight = (masked / level / (n * t)).astype(np.float32)
    return {"noisy_ids": np.where(masked, mask_id, toks)[:, :, None],
            "token_ids": toks[:, :, None],
            "loss_weight": weight[:, :, None]}


def block_of(batch, lo, hi):
    return {"noisy": batch["noisy_ids"][lo:hi, :, 0].astype(np.int32),
            "tok": batch["token_ids"][lo:hi, :, 0].astype(np.int32),
            "weight": batch["loss_weight"][lo:hi, :, 0]}


def expected_held_rows(config, traffic):
    """Rows a step sends to the held experts of one layer: every pick of
    the 2T rows where absent experts are folded onto them, else that times
    held / routed if routing is even. The static counts (`train_flops`)
    use it; what a step really sent is in the `moe.load` spans."""
    s = sizes(config, traffic)
    pairs = model_rows(traffic) * s["top_k"]
    return pairs if s["absent"] == "folded" \
        else pairs * s["held"][1] // s["routed"]


def train_flops(config, traffic):
    """Per-step training FLOPs: matmul terms only, backward twice the
    forward, recomputed operations not counted; the layers over the 2T rows
    a document's two copies make, attention by the (query, key) pairs the
    block-diffusion mask lets through (T^2 + T L a head), the experts by
    `expected_held_rows`, the head over the noisy half's T rows."""
    s = sizes(config, traffic)
    batch, rows, d, dh = batch_rows(traffic), model_rows(traffic), s["d"], \
        s["dh"]
    held = expected_held_rows(config, traffic)
    layer = 2 * rows * d * (s["hq"] + 2 * s["hkv"]) * dh \
        + 2 * rows * s["hq"] * dh * d \
        + 2 * s["hq"] * batch * flops_bd.visible_area_bd(
            s["t"], s["length"]) * 2 * dh \
        + 2 * rows * d * s["routed"] \
        + 2 * held * (d * 2 * s["moe_ff"] + s["moe_ff"] * d)
    fwd = s["layers"] * layer + 2 * tokens_per_step(traffic) * d * s["vocab"]
    return 3 * fwd


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, one dict a (layer, kernel
    kind) in `flops_bd.attention_call_flops`' form (`seq` the document's T:
    the call runs 2T rows); recompute runs the forward kernel twice a
    layer."""
    s = sizes(config, traffic)
    calls = []
    for _i in range(s["layers"]):
        if 4 * s["t"] * s["t"] <= 256 * 256:
            continue
        shape = {"batch": traffic["batch_per_chip"], "q_heads": s["hq"],
                 "kv_heads": s["hkv"], "seq": s["t"],
                 "block_length": s["length"], "d_qk": s["dh"],
                 "d_v": s["dh"]}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


def gmm_calls(config, traffic):
    """The grouped-matmul calls of one step, one dict a (layer, matrix): the
    layer's name as its `moe.load` span gives it, K, N, the groups, and how
    often each kernel runs (recompute runs the forward twice)."""
    s = sizes(config, traffic)
    return [{"layer": PREFIX % i, "k": k, "n": n, "groups": s["held"][1],
             "fwd": 2, "dx": 1, "dw": 1}
            for i in range(s["layers"])
            for k, n in ((s["d"], 2 * s["moe_ff"]), (s["moe_ff"], s["d"]))]


# ---- the plain reference -------------------------------------------------

rms_norm = lfm.rms_norm


#: (picks (tokens, k) over all experts, their weights (tokens, k)): the top-k
#: of the logits x Wr, a softmax over the picks' logits: SmallThinker's router
route = smallthinker.route


def expert_ffn(x, w_router, w13, w2, s, mm, held=None):
    """The part of the expert layer that the experts `held` = (first,
    count) give for the tokens x (tokens, d); w13 (count, d, 2F), w2
    (count, F, d) are THEIR matrices. A dense masked sum: every held expert
    over every token, times the token's weight for it (0 where it did not
    pick it); where absent experts are folded, a pick counts for the held
    expert congruent to it."""
    import jax
    import jax.numpy as jnp
    first, count = held or s["held"]
    picks, weights = route(x, w_router, s, mm)
    if s.get("absent") == "folded":
        picks = first + (picks - first) % count
    ids = first + jnp.arange(count)
    gates = jnp.sum(weights[:, :, None]
                    * (picks[:, :, None] == ids[None, None, :]), axis=1)

    def chunk(args):
        xc, gc = args

        def one(acc, e):
            w13_e, w2_e, gate_e = e
            return acc + gate_e[:, None] * lfm._gated(xc, w13_e, w2_e,
                                                      mm), None

        acc, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(xc),
                              (w13, w2, gc.T))
        return acc

    return lfm._chunked(chunk, (x, gates), MLP_CHUNK)


def visible(q_row, k_row, length, t):
    """Bool (queries, keys) of the block-diffusion mask over the 2T rows
    [noisy | clean]: the three lines of the rule."""
    import jax.numpy as jnp
    q_noisy, k_noisy = (q_row < t)[:, None], (k_row < t)[None, :]
    q_blk, k_blk = (q_row % t // length)[:, None], \
        (k_row % t // length)[None, :]
    return jnp.where(q_noisy,
                     jnp.where(k_noisy, k_blk == q_blk, k_blk < q_blk),
                     ~k_noisy & (k_blk <= q_blk))


def _attention(u, w, s, mm):
    """Grouped-query attention over the 2T rows u (n, 2T, d), ONE KEY HEAD
    AND ITS GROUP OF QUERY HEADS AT A TIME (a scan over the groups' slices
    of Wqkv and Wo under a checkpoint a group, so that no array of all
    heads' queries, keys, values or outputs is held), a head's queries in
    blocks against the mask `visible` writes out."""
    import jax
    import jax.numpy as jnp
    n, rows, d = u.shape
    hq, hkv, dh, t = s["hq"], s["hkv"], s["dh"], s["t"]
    group = hq // hkv
    bq = lfm._fit(rows, Q_BLOCK)
    key_row = jnp.arange(rows)

    def turned(m, scale):
        """(heads, n, 2T, D): per-head RMS norm, then each half of T rows
        turned apart: positions 0..T-1 twice."""
        m = rms_norm(m, scale, s["eps"])
        halves = m.reshape(m.shape[:2] + (2, t, dh))
        return lfm.rotate_half(halves, s["theta"]).reshape(m.shape)

    @jax.checkpoint
    def block(qb, first_row, kh, vh):
        """One head's queries [first_row, first_row + bq): qb (n, bq, dh)
        against kh, vh (n, 2T, dh)."""
        scores = mm(qb, kh.transpose(0, 2, 1)) * dh ** -0.5
        seen = visible(first_row + jnp.arange(bq), key_row, s["length"], t)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    @jax.checkpoint
    def one_group(out, slices):
        wq, wk, wv, wo = slices  # (d, group D), (d, D), (d, D), (group D, d)
        q = turned(mm(u, wq).reshape(n, rows, group, dh).transpose(
            2, 0, 1, 3), w["q_norm_s"])             # (group, n, 2T, dh)
        kh = turned(mm(u, wk)[None], w["k_norm_s"])[0]
        vh = mm(u, wv)

        def head(qh):
            got = jax.lax.map(
                lambda a: block(a[0], a[1], kh, vh),
                (qh.reshape(n, rows // bq, bq, dh).transpose(1, 0, 2, 3),
                 jnp.arange(rows // bq) * bq))
            return got.transpose(1, 0, 2, 3).reshape(n, rows, dh)

        o = jax.lax.map(head, q)                    # (group, n, 2T, dh)
        return out + mm(o.transpose(1, 2, 0, 3).reshape(
            n, rows, group * dh), wo), None

    wqkv = w["qkv.w_0"]
    by_kv_head = lambda m, width: m.reshape(d, hkv, width).transpose(1, 0, 2)
    out, _ = jax.lax.scan(one_group, jnp.zeros_like(u), (
        by_kv_head(wqkv[:, :hq * dh], group * dh),
        by_kv_head(wqkv[:, hq * dh:(hq + hkv) * dh], dh),
        by_kv_head(wqkv[:, (hq + hkv) * dh:], dh),
        w["out.w_0"].reshape(hkv, group * dh, d)))
    return out


def _layer(h, w, s, mm):
    import jax
    n, rows, d = h.shape
    u = rms_norm(h, w["attn_norm_s"], s["eps"])
    h1 = h + jax.checkpoint(
        lambda u_, w_: _attention(u_, w_, s, mm))(u, w)
    x = rms_norm(h1, w["ffn_norm_s"], s["eps"])
    part = jax.checkpoint(lambda x_, w_: expert_ffn(
        x_.reshape(n * rows, d), w_["router.w_0"], w_["experts_gate_up"],
        w_["experts_down"], s, mm))(x, w)
    return h1 + part.reshape(n, rows, d)


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: the sum over its
    sequences' noisy rows of weight x cross-entropy against the clean token
    at the same position (the weights carry the 1 / (B T))."""
    import jax
    import jax.numpy as jnp
    s = sizes(config, traffic)
    n, t = blk["tok"].shape
    rows = jnp.concatenate([blk["noisy"], blk["tok"]], axis=1)   # (n, 2T)
    x = p["sdar_word_embedding"][rows]
    for i in range(s["layers"]):
        prefix = PREFIX % i + "_"
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, s=s, mm=mm))(x, w)
    x = rms_norm(x[:, :t], p["sdar_norm_f_s"], s["eps"])
    head = p["sdar_lm_head"]
    ce = lfm._chunked(
        # (vocab, d) x (d, rows), then the small product turned: the head
        # is never transposed
        lambda a: ref.cross_entropy(mm(head, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["tok"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["weight"].reshape(-1))
