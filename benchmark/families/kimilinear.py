"""Kimi-Linear-48B-A3B causal LM training (`kimi_linear`: Kimi Delta
Attention and latent attention mixers, three to one, over sparse experts
with a shared expert; arXiv:2510.26692 and the published `modeling_kimi.py`):
the program through paddle_tpu's normal path, the seeded batch generator,
the required-FLOPs count, the parameter list and the plain reference.

The equations (program and reference implement exactly these; d = hidden,
H = heads held, K = V = 128 a KDA head, r = the gates' rank, E = experts
routed over, k = picks a token): every layer is h = x + Mixer(RMS(x; g1)),
y = h + FFN(RMS(h; g2)), RMS(x; g) = x / sqrt(mean(x^2) + eps) * g, no bias
anywhere; a final norm; an untied head. Mixers by `layer_kinds`, u the
normed input:
  kda   [q, k, v] = silu(conv(u Wqkv)), conv depthwise and causal, width
        `short_conv_kernel_size`, zeros before t = 0, one filter a channel;
        q, k <- each head's K numbers over sqrt(their sum of squares + 1e-6);
        g = -exp(A_log[h]) * softplus((u Wfa) Wfb + dt_bias) per token, head
        and key channel; beta = sigmoid(u Wbeta), one a head;
        S_0 = 0 (K x V a head); S' = Diag(exp(g_t)) S_{t-1};
        S_t = S' - beta_t k_t (k_t^T S') + beta_t k_t v_t^T;
        o_t = S_t^T q_t K^-1/2;
        out = (RMS over each head of o_t; scale of V) * sigmoid((u Wga) Wgb),
        then Wo.
  mla   q = u Wq -> (H, 128 + 64); [c | k_pe] = u Wkva -> (512 | 64);
        [k_nope | v] = RMS(c; scale of 512) Wkvb -> (H, 128 | 128);
        k = [k_nope | k_pe, the same for every head]; causal
        softmax(q k^T / sqrt(192)) v; out = concat Wo. No positions
        (`mla_use_nope`): the 64 "rope" numbers are never turned.
FFN of layer i: dense where its PUBLISHED index (from 1, as
`linear_attn_config` numbers layers) <= `first_k_dense_replace`,
Wd(silu(Wg u) * Wu u) with [Wg, Wu] one (d, 2 ff) matrix; else
shared(u) + experts(u): shared the same gated MLP at the experts' width,
every token, on every rank; experts as `families/lfm2moe.py` states them
(sigmoid scores over all E in float32, top-k of scores + a bias that stays at
zeros, the picks' scores over their sum + 1e-6, times
`routed_scaling_factor`; the sum over the picks THAT ARE HELD HERE).
Head: logits = RMS(x; gf) Whead^T over the rows held; loss = mean
cross-entropy over every position.

The reference is float32 `jax.numpy` at `highest`, imports nothing of
paddle_tpu and has no kernels. **KDA is the recurrence above, a token at a
time** (`lax.scan` over T; `jax.checkpoint` over blocks of steps, so that its
backward holds a state a block and not a state a token): another algorithm
than the program's chunked form, on purpose. Its state products are
elementwise float32 whatever `mm` is (the control's float8 rounds the
operands of the projections, not the state); MLA is explicit scores per head
and query block; the experts are `lfm2moe`'s dense masked sum. It is BLOCKED
like `lfm2moe`'s so that it fits beside `reference.follow`'s copies of the
parameters: `jax.checkpoint` per layer and again per mixer and FFN, token
chunks for the MLPs, the experts and the head. Blocking changes no value.

The chip's share (`reduced_why` and `deployment` in the configuration's
file): `heads_held` of both mixers' heads, `experts_held` of the experts,
the first rows of the vocabulary. The reference is given the same share;
`mixer_part` and `lfm2moe.expert_ffn(held=)` give any other rank's part, for
the test that adds the shares up.
"""
import functools

import numpy as np

from benchmark import flops_kda
from benchmark import reference as ref
from benchmark.families import lfm2moe as lfm

MLP_CHUNK = lfm.MLP_CHUNK
Q_BLOCK = lfm.Q_BLOCK
SCAN_BLOCK = 128        # tokens of the recurrence under one checkpoint
L2_EPS = 1e-6


def sizes(config):
    lin = config["linear_attn_config"]
    held, heads = config["experts_held"], config["heads_held"]
    if held[1] != config["num_experts"]:
        raise ValueError("num_experts %r is not the count experts_held %r "
                         "holds" % (config["num_experts"], held))
    if heads[1] != config["num_attention_heads"]:
        raise ValueError("num_attention_heads %r is not the count "
                         "heads_held %r holds"
                         % (config["num_attention_heads"], heads))
    return {"d": config["hidden_size"], "ff": config["intermediate_size"],
            "moe_ff": config["moe_intermediate_size"],
            "heads": (int(heads[0]), int(heads[1])),
            "dk": lin["head_dim"], "k": lin["short_conv_kernel_size"],
            "rank": config.get("gate_low_rank", lin["head_dim"]),
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "kv_rank": config["kv_lora_rank"],
            "routed": config["num_experts_routed"],
            "held": (int(held[0]), int(held[1])),
            "top_k": config["num_experts_per_token"],
            "shared": config["num_shared_experts"],
            "dense": config["first_k_dense_replace"],
            "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
            "norm_topk": config["moe_renormalize"],
            "scaling": config["routed_scaling_factor"],
            "kinds": list(config["layer_kinds"]),
            "published": list(config["published_layer_index"])}


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import kimi_linear
    cfg = kimi_linear.KimiLinearConfig.from_published(
        config, gate_rank=sizes(config)["rank"], dtype=config["precision"],
        recompute=True)
    main, startup, _feeds, fetch = kimi_linear.kimi_linear_pretrain_program(
        cfg, batch_rows(traffic), traffic["seq_len"],
        optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


batch_rows = lfm.batch_rows
tokens_per_step = lfm.tokens_per_step
make_batch = lfm.make_batch
block_of = lfm.block_of


def expected_held_rows(config, traffic):
    """Rows a step sends to the held experts of one layer if routing is
    even: tokens x picks x held / routed (`train_flops` uses it; what a
    step really sent is in the `moe.load` spans)."""
    s = sizes(config)
    return tokens_per_step(traffic) * s["top_k"] * s["held"][1] \
        // s["routed"]


def is_dense(s, i):
    return s["published"][i] <= s["dense"]


def layer_specs(config, i):
    """{suffix: (shape, dtype, init kind)} of layer i."""
    s = sizes(config)
    d, enc, h = s["d"], config["precision"], s["heads"][1]
    out = {"attn_norm_s": ((d,), "float32", "ones"),
           "ffn_norm_s": ((d,), "float32", "ones")}
    if s["kinds"][i] == "kda":
        wide = h * s["dk"]
        out.update({
            "kda_qkv.w_0": ((d, 3 * wide), enc, "normal"),
            "kda_qkv_conv.w_0": ((s["k"], 3 * wide), enc, "normal"),
            "kda_f_a.w_0": ((d, s["rank"]), enc, "normal"),
            "kda_f_b.w_0": ((s["rank"], wide), enc, "normal"),
            "kda_A_log": ((h,), "float32", "zeros"),
            "kda_dt_bias": ((wide,), "float32", "zeros"),
            "kda_beta.w_0": ((d, h), enc, "normal"),
            "kda_g_a.w_0": ((d, s["rank"]), enc, "normal"),
            "kda_g_b.w_0": ((s["rank"], wide), enc, "normal"),
            "kda_o_norm_s": ((s["dk"],), "float32", "ones"),
            "kda_out.w_0": ((wide, d), enc, "normal")})
    else:
        out.update({
            "mla_q.w_0": ((d, h * (s["nope"] + s["rope"])), enc, "normal"),
            "mla_kv_a.w_0": ((d, s["kv_rank"] + s["rope"]), enc, "normal"),
            "mla_kv_a_norm_s": ((s["kv_rank"],), "float32", "ones"),
            "mla_kv_b.w_0": ((s["kv_rank"], h * (s["nope"] + s["dv"])), enc,
                             "normal"),
            "mla_out.w_0": ((h * s["dv"], d), enc, "normal")})
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
    and the head, ones for norm scales, zeros for `A_log` and `dt_bias`
    (`assumed` in the configuration's file says why). Block matrices and the
    conv weights are held in `precision`; norms, `A_log`, `dt_bias`, the
    router, the table and the head in float32. The expert bias is no
    parameter (a buffer of zeros in the program)."""
    s = sizes(config)
    specs = {"kimi_word_embedding": ((s["vocab"], s["d"]), "float32",
                                     "normal"),
             "kimi_lm_head": ((s["vocab"], s["d"]), "float32", "normal"),
             "kimi_norm_f_s": ((s["d"],), "float32", "ones")}
    for i in range(len(s["kinds"])):
        for suffix, spec in layer_specs(config, i).items():
            specs["kimi_layer_%d_%s" % (i, suffix)] = spec
    return specs


def train_flops(config, traffic):
    """Per-step training FLOPs: matmul terms only, backward twice the
    forward, recomputed operations not counted; projections and gates at
    the heads held; MLA's scores and values by the area a query can see;
    the experts by `expected_held_rows` (static: even routing); the delta
    rule by its recurrent form's required work (`flops_kda`)."""
    s = sizes(config)
    batch, seq = batch_rows(traffic), traffic["seq_len"]
    tokens, d, h = batch * seq, s["d"], s["heads"][1]
    rows = expected_held_rows(config, traffic)
    gated = lambda n, width: 2 * n * (d * 2 * width + width * d)
    fwd = 0
    for i, kind in enumerate(s["kinds"]):
        if kind == "kda":
            wide = h * s["dk"]
            fwd += 2 * tokens * (d * 3 * wide + wide * d + d * h
                                 + 2 * (d * s["rank"] + s["rank"] * wide))
            fwd += flops_kda.call_flops(batch, seq, h, s["dk"], s["dk"])[0]
        else:
            d_qk = s["nope"] + s["rope"]
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
    for kind in s["kinds"]:
        if kind != "mla" or t * t <= 256 * 256:
            continue
        shape = {"batch": traffic["batch_per_chip"],
                 "q_heads": s["heads"][1], "kv_heads": s["heads"][1],
                 "seq": t, "d_qk": s["nope"] + s["rope"], "d_v": s["dv"],
                 "window": None}
        calls.append(dict(shape, kind="forward", count=2))
        calls.append(dict(shape, kind="backward", count=1))
    return calls


# ---- the plain reference -------------------------------------------------

rms_norm = lfm.rms_norm


def _gated_mlp(u, w13, w2, mm):
    n, t, d = u.shape
    return lfm._chunked(lambda a: lfm._gated(a[0], w13, w2, mm),
                        (u.reshape(n * t, d),), MLP_CHUNK).reshape(n, t, d)


def delta_rule(q, k, v, g, beta, scale):
    """The gated delta rule a token at a time. q, k, g (n, t, H, K),
    v (n, t, H, V), beta (n, t, H) -> o (n, t, H, V). The state's products
    are elementwise float32 sums."""
    import jax
    import jax.numpy as jnp
    n, t, h, dk = q.shape
    blk = lfm._fit(t, SCAN_BLOCK)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        seen = jnp.sum(k_t[..., None] * s, axis=-2)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return s, jnp.sum((q_t * scale)[..., None] * s, axis=-2)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((t // blk, blk) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    _s, out = jax.lax.scan(block, jnp.zeros((n, h, dk, v.shape[-1]),
                                            jnp.float32), xs)
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def _conv_silu(x, w):
    import jax
    import jax.numpy as jnp
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + t] * w[i] for i in range(k)))


def _kda(u, w, s, mm):
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    dk = s["dk"]
    h = w["kda_A_log"].shape[0]

    def heads(m):
        return m.reshape(n, t, h, dk)

    def unit(m):
        return m * jax.lax.rsqrt(jnp.sum(jnp.square(m), axis=-1,
                                         keepdims=True) + L2_EPS)

    q, k, v = (heads(m) for m in jnp.split(_conv_silu(
        mm(u, w["kda_qkv.w_0"]), w["kda_qkv_conv.w_0"]), 3, axis=-1))
    g = -jnp.exp(w["kda_A_log"])[:, None] * jax.nn.softplus(heads(
        mm(mm(u, w["kda_f_a.w_0"]), w["kda_f_b.w_0"]) + w["kda_dt_bias"]))
    beta = jax.nn.sigmoid(mm(u, w["kda_beta.w_0"]))
    o = delta_rule(unit(q), unit(k), v, g, beta, dk ** -0.5)
    gate = jax.nn.sigmoid(mm(mm(u, w["kda_g_a.w_0"]), w["kda_g_b.w_0"]))
    o = rms_norm(o, w["kda_o_norm_s"], s["eps"]).reshape(n, t, h * dk)
    return mm(o * gate, w["kda_out.w_0"])


def _mla(u, w, s, mm):
    import jax
    import jax.numpy as jnp
    n, t, _d = u.shape
    nope, rope, dv = s["nope"], s["rope"], s["dv"]
    d_qk = nope + rope
    h = w["mla_q.w_0"].shape[1] // d_qk
    q = mm(u, w["mla_q.w_0"]).reshape(n, t, h, d_qk).transpose(2, 0, 1, 3)
    latent, k_pe = jnp.split(mm(u, w["mla_kv_a.w_0"]), [s["kv_rank"]],
                             axis=-1)
    kv = mm(rms_norm(latent, w["mla_kv_a_norm_s"], s["eps"]),
            w["mla_kv_b.w_0"]).reshape(n, t, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (n, t, h, rope))], axis=-1)
    k, v = k.transpose(2, 0, 1, 3), kv[..., nope:].transpose(2, 0, 1, 3)
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

    def head(args):
        qh, kh, vh = args
        rows = jax.lax.map(
            lambda a: block(a[0], a[1], kh, vh),
            (qh.reshape(n, t // bq, bq, d_qk).transpose(1, 0, 2, 3),
             jnp.arange(t // bq) * bq))
        return rows.transpose(1, 0, 2, 3).reshape(n, t, dv)

    o = jax.lax.map(head, (q, k, v))                # (h, n, t, dv)
    return mm(o.transpose(1, 2, 0, 3).reshape(n, t, h * dv), w["mla_out.w_0"])


def mixer_part(u, w, kind, s, mm):
    """The part of layer kind `kind`'s mixer that the heads whose columns
    and rows `w` holds give (the count is read from `w`'s shapes)."""
    return (_kda if kind == "kda" else _mla)(u, w, s, mm)


def ffn_part(u, w, s, mm, held=None, shared=True):
    """The expert layer's part for the experts `held` (default: the
    configuration's), with the shared expert where `shared`."""
    n, t, d = u.shape
    out = lfm.expert_ffn(u.reshape(n * t, d), w["router.w_0"],
                         w["experts_gate_up"], w["experts_down"], s, mm,
                         held=held).reshape(n, t, d)
    if shared and s["shared"]:
        out = out + _gated_mlp(u, w["shared_gate_up.w_0"],
                               w["shared_down.w_0"], mm)
    return out


def _layer(x, w, i, s, mm):
    import jax
    u = rms_norm(x, w["attn_norm_s"], s["eps"])
    h = x + jax.checkpoint(lambda u_, w_: mixer_part(
        u_, w_, s["kinds"][i], s, mm))(u, w)
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
    x = p["kimi_word_embedding"][blk["tok"]]
    for i in range(len(s["kinds"])):
        prefix = "kimi_layer_%d_" % i
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_layer, i=i, s=s, mm=mm))(x, w)
    x = rms_norm(x, p["kimi_norm_f_s"], s["eps"])
    head = p["kimi_lm_head"]
    ce = lfm._chunked(
        lambda a: ref.cross_entropy(mm(head, a[0].T).T, a[1]),
        (x.reshape(n * t, -1), blk["lbl"].reshape(-1)), MLP_CHUNK)
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
