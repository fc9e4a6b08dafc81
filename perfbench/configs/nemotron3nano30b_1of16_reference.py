"""``nemotron3nano30b_1of16``'s plain reference: Nemotron-3-Nano-30B-A3B's forward pass
(``nemotron_h``: blocks of one part each, Mamba-2 mixers computed by their sequential
recurrence, GQA attention without a positional encoding, a sigmoid router with a selection
bias and scaled weights, non-gated relu-squared experts beside a shared one, an untied
head), GAE, the clipped PPO loss over the program's sequence minibatches and clipped Adam
in straightforward float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of ``sheeprl_tpu`` and nothing of the other configurations' references.

The blocks, as ``configs/nemotron3nano30b_1of16.json`` states them (``assumed`` there lists
what the published config does not say).  Residual stream ``x``, block ``l`` of kind
``pattern[l]``: ``out = x + Part_l(RMSNorm(x))``.

* ``M``, the Mamba-2 mixer: ``[z | xBC | dt] = a W_in``; ``xBC = silu(sum_j w_j xBC_{t-j} +
  bias)`` over ``conv_kernel`` inputs of the token's own episode; ``xBC = [x | B | C]``, ``x``
  by head (``mamba_heads`` of ``mamba_head_dim``), ``B`` and ``C`` by group (``ssm_groups`` of
  ``ssm_state``; head ``h`` reads group ``h // (heads / groups)``); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``state_t = exp(dt_t A) state_{t-1} + dt_t x_t
  (outer) B_t`` from an empty state at an episode's start, ``y_t = state_t C_t + D x_t``; then
  ``y = GroupRMSNorm(y * silu(z))`` over the groups' ``heads x head_dim / groups`` channels,
  ``out = y W_out``;
* ``*``, attention: ``q = a W_q``, ``k = a W_k``, ``v = a W_v`` (``kv_heads_held`` key heads,
  ``heads_held / kv_heads_held`` query heads each), no rotation; key ``j`` visible to query
  ``i`` iff same episode and ``pos_j <= pos_i``; scores over ``sqrt(head_dim)``; ``y =
  concat_h(p v) W_o``;
* ``E``, the experts: ``s = sigmoid(m W_r)`` over all the experts; the ``experts_per_token``
  with the largest ``s + b`` are chosen; their weights are ``s`` without ``b`` over (their sum
  + ``router_eps``), times ``routed_scale``; ``y = sum over the experts held, among the
  token's, of w_e relu(m U^e)^2 V^e``, plus the shared expert ``relu(m S_u)^2 S_d`` for every
  token, unscaled.  ``b`` is a constant;
* after the blocks: RMSNorm, logits ``hidden @ W_head`` over the rows held of the untied
  head, and a linear value head.  Input: ``E[token] + (1 - is_first) E[previous action]``.

The Mamba mixer is computed by its **recurrence, token by token** (``lax.scan`` over the
tokens, the state ``[heads, head_dim, state]`` carried): nothing is chunked, so it shares no
form with the program's scan.  There is no cache and no slot: every token of an env so far
is a row of plain arrays in the order it came, each with its episode and position (room
for the rollouts followed is reserved at the start, rows not yet written belong to no
episode); the attention mask is made from those.  What the algorithm itself carries is
carried, as a constant of the update (``stop_gradient`` where it is read): an attention
block's keys and values of earlier rollouts, and a Mamba block's state and last
``conv_kernel - 1`` convolution inputs (with their episodes) at the rollout's start, all as
the weights of their time produced them.  Every expert held is computed densely for every
token and weighted by its routing weight (zero where the token did not choose it).  Each
block is recomputed in the backward pass, and the recurrence in blocks of 16 tokens, so
that the gradient fits the chip.

``quant``: ``"f32"`` is the reference; ``"bf16"`` / ``"fp8"`` round every matmul operand of the
model to that precision first (the router stays float32, as stated), the recurrence's
products too; ``"bf16_state"`` keeps every product in float32 and rounds the recurrence's
state to bfloat16 after every token: the controls of the comparison that decides
``correct``.  ``sizes.ssm_carry`` false drops the carried state: the planted fault.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2 = 0.9, 0.999
RECURRENCE_BLOCK = 16  # tokens of the recurrence recomputed together in the backward pass


# --------------------------------------------------------------------------- weights
def _mamba_widths(S):
    inner = S["mamba_heads"] * S["mamba_head_dim"]
    return inner, inner + 2 * S["ssm_groups"] * S["ssm_state"]


def layer_shapes(S: Dict[str, Any], l: int) -> Dict[str, Tuple[int, ...]]:
    D, kind = S["hidden_size"], S["pattern"][l]
    if kind == "M":
        H, (inner, width) = S["mamba_heads"], _mamba_widths(S)
        out = {
            "mamba_norm": (D,), "mamba_in": (D, inner + width + H), "mamba_conv": (S["conv_kernel"], width), "mamba_conv_bias": (width,),
            "dt_bias": (H,), "A_log": (H,), "D": (H,), "mamba_gate_norm": (inner,), "mamba_out": (inner, D),
        }  # fmt: skip
    elif kind == "*":
        q, kv = S["heads_held"] * S["head_dim"], S["kv_heads_held"] * S["head_dim"]
        out = {"attn_norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv), "wo": (q, D)}
    else:
        E, F, Fs = S["experts_held"], S["expert_width"], S["shared_width"]
        out = {
            "ffn_norm": (D,), "router": (D, S["num_experts"]), "expert_bias": (S["num_experts"],), "w_up": (E, D, F), "w_down": (E, F, D),
            "shared_up": (D, Fs), "shared_down": (Fs, D),
        }  # fmt: skip
    return dict(sorted(out.items()))


def shapes(S: Dict[str, Any]) -> Dict[str, Any]:
    D, V = S["hidden_size"], S["vocab_held"]
    tree: Dict[str, Any] = {"embed": (V, D), "final_norm": (D,), "head": (D, V), "value_b": (1,), "value_w": (D, 1)}
    for l in range(S["layers"]):
        tree[f"layers_{l}"] = layer_shapes(S, l)
    return {"params": tree}


def _flat(S):
    return jax.tree_util.tree_flatten_with_path(shapes(S), is_leaf=lambda x: isinstance(x, tuple))


def flat_shapes(S: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    return {"/".join(str(k.key) for k in path): shape for path, shape in _flat(S)[0]}


def make_weights(S: Dict[str, Any], seed) -> Dict[str, Any]:
    """The benchmark's weights from the seed.  Matmul weights are normal with variance
    1 / fan-in; the branches' output projections (``wo``, ``mamba_out``, the routed and shared
    down-projections) are scaled by ``S["branch_scale"]`` and the router by
    ``S["router_scale"]``, so that the stream is mostly the exact embedding sum and few of
    the top-k choices sit on a tie that bf16 rounding flips.  The Mamba mixer's own leaves
    follow the family's initialisation: ``A_log = log(h + 1)`` for head ``h``, ``dt_bias`` the
    inverse softplus of steps drawn log-uniform in ``[time_step_min, time_step_max]`` (at
    least ``time_step_floor``), ``D`` near 1; so some heads keep a token for thousands of
    steps and others forget it in a few, and the carried state and the states passed
    between chunks both count.  The embedding has unit variance; the head is normal with
    variance 1 / D and reads a normed state: logits of order one.  ``expert_bias`` is normal
    with deviation ``S["bias_scale"]``, a constant of the run."""
    flat, treedef = _flat(S)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    leaves = []
    for (path, shape), key in zip(flat, keys):
        name = str(path[-1].key)
        if name.endswith("norm") or name == "D":
            w = 1.0 + 0.1 * jax.random.normal(key, shape)
        elif name == "value_b":
            w = jnp.zeros(shape)
        elif name == "embed":
            w = jax.random.normal(key, shape)
        elif name == "expert_bias":
            w = S["bias_scale"] * jax.random.normal(key, shape)
        elif name == "A_log":
            w = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif name == "dt_bias":
            lo, hi = np.log(S["time_step_min"]), np.log(S["time_step_max"])
            step = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi)), S["time_step_floor"])
            w = step + jnp.log(-jnp.expm1(-step))
        elif name == "mamba_conv_bias":
            w = 0.1 * jax.random.normal(key, shape)
        else:
            down = ("wo", "w_down", "shared_down", "mamba_out")
            scale = {"router": S["router_scale"], "value_w": 0.5}.get(name, S["branch_scale"] if name in down else 1.0)
            w = jax.random.normal(key, shape) * (scale / np.sqrt(shape[-2]))
        leaves.append(w.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_groups(S: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Leaves of ``jax.tree.leaves(params)`` pooled by kind for ``grad_gap.<group>``."""
    names = [str(p[-1].key) for p, _ in _flat(S)[0]]
    kinds = {
        "mamba": ("mamba_in", "mamba_conv", "mamba_conv_bias", "dt_bias", "A_log", "D", "mamba_gate_norm", "mamba_out"),
        "attention": ("wq", "wk", "wv", "wo"),
        "shared": ("shared_up", "shared_down"),
        "experts": ("w_up", "w_down"),
        "router": ("router",),
        "tables": ("embed", "head"),
    }
    return {g: {"leaves": [i for i, n in enumerate(names) if n in ks], "by": "pooled"} for g, ks in kinds.items()}


# --------------------------------------------------------------------------- the model
def _rounder(quant: str):
    if quant in ("f32", "bf16_state"):
        return lambda x: x
    dtype = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[quant]
    # the value rounded, the gradient passed straight through (a cast to fp8 alone stops it)
    return lambda x: x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def empty_context(S: Dict[str, Any], n: int, room: int) -> Dict[str, Any]:
    """Room for ``room`` tokens an env; a row not yet written has episode ``-1``, which no
    token is of (the first episode is 1).  An attention block keeps every token's key and
    value; a Mamba block its state and its last convolution inputs with their episodes,
    as they stand after the env's last token (``last_ep``'s episode)."""
    layers = []
    for kind in S["pattern"][: S["layers"]]:
        if kind == "M":
            _, width = _mamba_widths(S)
            back = S["conv_kernel"] - 1
            ssm = jnp.zeros((n, S["mamba_heads"], S["mamba_head_dim"], S["ssm_state"]), jnp.float32)
            layers.append({"ssm": ssm, "conv": jnp.zeros((n, back, width), jnp.float32), "conv_ep": jnp.full((n, back), -1, jnp.int32)})
        elif kind == "*":
            kv = lambda: jnp.zeros((n, room, S["kv_heads_held"], S["head_dim"]), jnp.float32)  # noqa: E731  a buffer each: the update is given them to overwrite
            layers.append({"k": kv(), "v": kv()})
        else:
            layers.append({})
    return {"layers": layers, "pos": jnp.zeros((n, room), jnp.int32), "ep": jnp.full((n, room), -1, jnp.int32), "filled": jnp.zeros((), jnp.int32), "last_ep": jnp.full((n,), -1, jnp.int32)}


def append(context: Dict[str, Any], made, pos, ep) -> Dict[str, Any]:
    at, put = context["filled"], jax.lax.dynamic_update_slice_in_dim
    layers = [{name: put(c[name], m[name], at, 1) for name in c} if "k" in c else m for c, m in zip(context["layers"], made)]
    return {"layers": layers, "pos": put(context["pos"], pos, at, 1), "ep": put(context["ep"], ep, at, 1), "filled": at + pos.shape[1], "last_ep": ep[:, -1]}


QUERY_BLOCK = 32  # queries whose scores over every key are formed (and recomputed in the backward pass) together


def attend(q, keys, vals, see, R, own=None):
    """Softmax attention, ``QUERY_BLOCK`` queries at a time: ``q``: ``[N, T, Hkv, G, hd]``,
    ``keys`` / ``vals``: ``[N, C, Hkv, hd]``, ``see``: ``[N, T, C]``; ``own``: each query's own
    key and value ``[N, T, Hkv, hd]``, which it sees besides, or ``None``."""
    N, T, Hkv, G, hd = q.shape
    b = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    blocks = lambda v: jnp.moveaxis(v.reshape(N, T // b, b, *v.shape[2:]), 1, 0)  # noqa: E731

    @jax.checkpoint
    def one(args):
        q, see, own = args
        s = jnp.where(see[:, None, None], jnp.einsum("ntkgd,nckd->nkgtc", R(q), R(keys)) / np.sqrt(hd), -jnp.inf)
        if own is not None:
            s = jnp.concatenate([s, jnp.einsum("ntkgd,ntkd->nkgt", R(q), R(own[0]))[..., None] / np.sqrt(hd)], -1)
        w = jax.nn.softmax(s, -1)
        o = jnp.einsum("nkgtc,nckd->ntkgd", R(w[..., : keys.shape[1]]), R(vals))
        if own is not None:
            o = o + jnp.einsum("nkgt,ntkd->ntkgd", R(w[..., -1]), R(own[1]))
        return o

    o = jax.lax.map(one, (blocks(q), blocks(see), None if own is None else tuple(blocks(v) for v in own)))
    return jnp.moveaxis(o, 0, 1).reshape(N, T, Hkv * G * hd)


def attention(S, L, x, ctx, context, pos, ep, R, side):
    """GQA over the context's rows and the chunk's own, no rotation.  ``side``: a token after
    each of the chunk's, which sees the rows before it in its episode and itself."""
    Hq, Hkv, hd = S["heads_held"], S["kv_heads_held"], S["head_dim"]
    G = Hq // Hkv

    def project(v):
        a = R(rms_norm(v, L["attn_norm"], S["norm_eps"]))
        return (a @ R(L["wq"])).reshape(*v.shape[:2], Hkv, G, hd), (a @ R(L["wk"])).reshape(*v.shape[:2], Hkv, hd), (a @ R(L["wv"])).reshape(*v.shape[:2], Hkv, hd)

    q, k, v = project(x)
    # the earlier rollouts' rows are constants of the update; the weights that read them are not
    keys = jnp.concatenate([jax.lax.stop_gradient(ctx["k"]), k], 1)
    vals = jnp.concatenate([jax.lax.stop_gradient(ctx["v"]), v], 1)
    k_pos, k_ep = jnp.concatenate([context["pos"], pos], 1), jnp.concatenate([context["ep"], ep], 1)
    see = (k_ep[:, None, :] == ep[:, :, None]) & (k_pos[:, None, :] <= pos[:, :, None])
    out = x + R(attend(q, keys, vals, see, R)) @ R(L["wo"])
    side_out = None
    if side is not None:
        xs, s_pos, s_ep = side
        qs, ks, vs = project(xs)
        before = (k_ep[:, None, :] == s_ep[:, :, None]) & (k_pos[:, None, :] < s_pos[:, :, None])
        side_out = xs + R(attend(qs, keys, vals, before, R, (ks, vs))) @ R(L["wo"])
    return out, side_out, {"k": k, "v": v}


def recurrence(A, state0, main, side, R, quant):
    """The Mamba-2 recurrence over the tokens, time-major: ``main = (x [T, N, H, P], dt [T, N,
    H], B, C [T, N, G, Ns], reset [T, N])``; ``side``: ``(x, dt, B, C)`` of a token after each
    main token, from the state that token left, or ``None``.  -> ``y`` (and the side's) ``[T,
    N, H, P]`` and the state after the last token.  ``A``: ``[H]``.  The heads of a group share
    its ``B`` and ``C`` and nothing else: the recurrence runs a group at a time (and is
    recomputed so in the backward pass), so that one group's states are held at once."""
    T, N, H, P = main[0].shape
    G = main[2].shape[2]
    K = H // G
    # the state rounded to bfloat16's 8 exponent and 7 mantissa bits by an operation the compiler keeps (a cast there and back it may drop)
    keep = (lambda h: jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)) if quant == "bf16_state" else (lambda h: h)

    def by_group(x, dt, b, c):  # -> [G, T, N, K, P], [G, T, N, K], [G, T, N, Ns] twice
        return jnp.moveaxis(x.reshape(T, N, G, K, P), 2, 0), jnp.moveaxis(dt.reshape(T, N, G, K), 2, 0), jnp.moveaxis(b, 2, 0), jnp.moveaxis(c, 2, 0)

    def advance(a, h, x, dt, b):
        return jnp.exp(dt * a)[..., None, None] * h + R(dt[..., None] * x)[..., None] * R(b)[:, None, None, :]

    def read(h, c):
        return jnp.einsum("nkpj,nj->nkp", R(h), R(c))

    block = RECURRENCE_BLOCK if T % RECURRENCE_BLOCK == 0 else T

    @jax.checkpoint
    def one_group(args):
        a, h0, mine, theirs = args

        def token(h, inp):
            (x, dt, b, c, reset), s = inp
            h = keep(advance(a, jnp.where(reset[:, None, None, None], 0.0, h), x, dt, b))
            if s is None:
                return h, (read(h, c),)
            xs, dts, bs, cs = s
            return h, (read(h, c), read(advance(a, h, xs, dts, bs), cs))

        @jax.checkpoint
        def run(h, blk):
            return jax.lax.scan(token, h, blk)

        blocked = jax.tree.map(lambda v: v.reshape(T // block, block, *v.shape[1:]), (mine, theirs))
        h, ys = jax.lax.scan(run, h0, blocked)
        return h, tuple(y.reshape(T, *y.shape[2:]) for y in ys)

    groups = (*by_group(*main[:4]), jnp.broadcast_to(main[4], (G, T, N)))
    side_groups = None if side is None else by_group(*side)
    h, ys = jax.lax.map(one_group, (A.reshape(G, K), jnp.moveaxis(state0.reshape(N, G, K, P, -1), 1, 0), groups, side_groups))
    heads = lambda y: jnp.moveaxis(y, 0, 2).reshape(T, N, H, P)  # noqa: E731  [G, T, N, K, P] -> [T, N, H, P]
    return heads(ys[0]), (heads(ys[1]) if side is not None else None), jnp.moveaxis(h, 0, 1).reshape(N, H, P, -1)


def mamba(S, L, x, ctx, context, ep, R, quant, side):
    """The Mamba-2 mixer over the chunk (and the side tokens), from the carried state and
    convolution inputs; returns what the chunk leaves for the next rollout."""
    N, T, _ = x.shape
    H, P, G, Ns, J = S["mamba_heads"], S["mamba_head_dim"], S["ssm_groups"], S["ssm_state"], S["conv_kernel"]
    inner, width = _mamba_widths(S)

    def project(v):
        proj = R(rms_norm(v, L["mamba_norm"], S["norm_eps"])) @ R(L["mamba_in"])
        return proj[..., :inner], proj[..., inner : inner + width], proj[..., inner + width :]

    def finish(u, dt_raw):
        xs, b, c = u[..., :inner], u[..., inner : inner + G * Ns], u[..., inner + G * Ns :]
        n, t = u.shape[:2]
        dt = jax.nn.softplus(dt_raw + L["dt_bias"])
        time = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
        return time(xs.reshape(n, t, H, P)), time(dt), time(b.reshape(n, t, G, Ns)), time(c.reshape(n, t, G, Ns))

    def out(y, xs, z):  # y, xs: [T, N, H, P]
        y = jnp.swapaxes(y + L["D"][:, None] * xs, 0, 1)
        y = y.reshape(N, T, inner) * jax.nn.silu(z)
        y = rms_norm(y.reshape(N, T, G, inner // G), L["mamba_gate_norm"].reshape(G, inner // G), S["norm_eps"]).reshape(N, T, inner)
        return R(y) @ R(L["mamba_out"])

    z, xbc, dt_raw = project(x)
    back = J - 1
    every = jnp.concatenate([jax.lax.stop_gradient(ctx["conv"]), xbc], 1)  # [N, back + T, width]
    every_ep = jnp.concatenate([ctx["conv_ep"], ep], 1)

    def taps(offset, first):  # bias + sum_j w_j of the input j steps before the token at back + offset + t, in the token's episode
        acc = L["mamba_conv_bias"]
        for j in range(first, J):
            at = slice(back + offset - j, back + offset - j + T)
            acc = acc + L["mamba_conv"][j] * jnp.where((every_ep[:, at] == ep)[..., None], every[:, at], 0.0)
        return acc

    main = finish(jax.nn.silu(taps(0, 0)), dt_raw)
    reset = jnp.swapaxes(ep != jnp.concatenate([context["last_ep"][:, None], ep[:, :-1]], 1), 0, 1)
    state0 = jax.lax.stop_gradient(ctx["ssm"]) if S.get("ssm_carry", True) else jnp.zeros_like(ctx["ssm"])
    side_in = None
    if side is not None:
        zs, xbc_s, dt_s = project(side[0])
        # the side token's own input, then the main tokens up to the one it follows (its episode's)
        side_in = finish(jax.nn.silu(L["mamba_conv"][0] * xbc_s + taps(1, 1)), dt_s)
    y, y_side, state = recurrence(-jnp.exp(L["A_log"]), state0, (*main, reset), side_in, R, quant)
    result = x + out(y, main[0], z)
    side_out = side[0] + out(y_side, side_in[0], zs) if side is not None else None
    made = {"ssm": state, "conv": every[:, -back:], "conv_ep": every_ep[:, -back:]}
    return result, side_out, made


def experts(S, L, x, R):
    """The routed experts held here and the shared expert, relu squared, no gate; returns the
    output and the experts each token chose."""
    normed = rms_norm(x, L["ffn_norm"], S["norm_eps"])
    m = R(normed)
    score = jax.nn.sigmoid(normed @ L["router"])
    _, top_i = jax.lax.top_k(score + L["expert_bias"], S["experts_per_token"])
    top_w = jnp.take_along_axis(score, top_i, -1)
    if S.get("norm_topk_prob", True):
        top_w = top_w / (top_w.sum(-1, keepdims=True) + S["router_eps"])
    top_w = top_w * S["routed_scale"]

    def one_expert(y, e_w):
        e, wu, wd = e_w
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)  # 0 where the token did not choose e
        act = jnp.square(jax.nn.relu(m @ R(wu)))
        return y + weight[..., None] * (R(act) @ R(wd)), None

    held = S.get("expert_offset", 0) + jnp.arange(S["experts_held"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (held, L["w_up"], L["w_down"]))
    if S.get("shared_here", True):  # every chip of the group computes the shared expert alike: a test that adds the shares up counts it once
        y = y + R(jnp.square(jax.nn.relu(m @ R(L["shared_up"])))) @ R(L["shared_down"])
    return x + y, top_i


def layer(S, l, L, x, context, pos, ep, quant="f32", side=None):
    """One block over a chunk ``x``: ``[N, T, D]`` (``L``: its weights; ``side``: ``(x, pos, ep)``
    of the side tokens, or ``None``) -> its output, the side's, what the chunk leaves in the
    context, and the experts each token chose ``[N, T, k]`` (``None`` for a mixer)."""
    R = _rounder(quant)
    kind, ctx = S["pattern"][l], context["layers"][l]
    if kind == "M":
        out, side_out, made = mamba(S, L, x, ctx, context, ep, R, quant, side)
        return out, side_out, made, None
    if kind == "*":
        out, side_out, made = attention(S, L, x, ctx, context, pos, ep, R, side)
        return out, side_out, made, None
    out, top_i = experts(S, L, x, R)
    return out, (experts(S, L, side[0], R)[0] if side is not None else None), {}, top_i


def forward(S, params, context, tokens, prev, is_first, pos, ep, quant="f32", side=None):
    """``tokens, prev, is_first, pos, ep``: ``[N, T]``; ``context``: what every earlier token
    of each env left (per block), with its position and episode; ``side``: ``(tokens, prev,
    pos, ep)`` of a token after each of the chunk's, or ``None``.  Returns the final normed
    hidden state ``[N, T, D]``, the values ``[N, T]``, what the chunk adds to the context per
    block, the experts chosen ``[expert blocks, N, T, k]`` and the side's hidden state and
    values (``None`` without a side)."""
    P = params["params"]
    x = P["embed"][tokens] + (1.0 - is_first)[..., None] * P["embed"][prev]
    xs = None if side is None else P["embed"][side[0]] + P["embed"][side[1]]
    made, chosen = [], []
    for l in range(S["layers"]):
        step = jax.checkpoint(lambda L, x, xs, context, l=l: layer(S, l, L, x, context, pos, ep, quant, None if xs is None else (xs, side[2], side[3])))
        x, xs, new, top_i = step(P[f"layers_{l}"], x, xs, context)
        made.append(new)
        if top_i is not None:
            chosen.append(top_i)
    head = lambda v: rms_norm(v, P["final_norm"], S["norm_eps"])  # noqa: E731
    value = lambda h: (h @ P["value_w"] + P["value_b"])[..., 0]  # noqa: E731
    hidden = head(x)
    side_out = None if xs is None else (head(xs), value(head(xs)))
    return hidden, value(hidden), made, jnp.stack(chosen), side_out


def log_probs(S, params, hidden, actions, quant="f32", block=512):
    """Log-probability of ``actions`` and the entropy, ``[N, T]``, under the softmax of
    ``hidden @ W_head`` (a table of its own, the rows held); formed ``block`` tokens at a time
    so that the whole fits beside the optimizer's state (plain arithmetic, blocked)."""
    R = _rounder(quant)
    N, T, D = hidden.shape
    head = R(params["params"]["head"])
    n = N * T
    block = min(block, n)
    pad = (-n) % block

    @jax.checkpoint
    def one(h, a):
        logp = jax.nn.log_softmax(R(h) @ head, -1)
        return jnp.take_along_axis(logp, a[:, None], 1)[:, 0], -(jnp.exp(logp) * logp).sum(-1)

    h = jnp.pad(hidden.reshape(n, D), ((0, pad), (0, 0))).reshape(-1, block, D)
    a = jnp.pad(actions.reshape(n), (0, pad)).reshape(-1, block)
    lp, ent = jax.lax.map(lambda t: one(*t), (h, a))
    return lp.reshape(-1)[:n].reshape(N, T), ent.reshape(-1)[:n].reshape(N, T)


# --------------------------------------------------------------------------- PPO
def episodes_and_positions(is_first: np.ndarray, ep0: np.ndarray, pos0: np.ndarray):
    """``is_first``: ``[N, T]``; ``ep0, pos0``: the episode and the next position each env
    had reached -> episode and position of every token, and where the envs stand after."""
    N, T = is_first.shape
    ep, pos = np.zeros((N, T), np.int32), np.zeros((N, T), np.int32)
    e, p = ep0.copy(), pos0.copy()
    for t in range(T):
        start = is_first[:, t] > 0
        e = np.where(start, e + 1, e)
        p = np.where(start, 0, p)
        ep[:, t], pos[:, t] = e, p
        p = p + 1
    return ep, pos, e, p


def gae(S, rewards, values, dones, next_value):
    """``[T, N]`` arrays; ``dones[t]``: the episode ended at step ``t``."""
    T = rewards.shape[0]
    adv = jnp.zeros_like(next_value)
    out = []
    for t in reversed(range(T)):
        nv = next_value if t == T - 1 else values[t + 1]
        alive = 1.0 - dones[t]
        delta = rewards[t] + S["gamma"] * nv * alive - values[t]
        adv = delta + S["gamma"] * S["gae_lambda"] * alive * adv
        out.append(adv)
    advantages = jnp.stack(out[::-1])
    return advantages + values, advantages


def init_state(params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    return {"params": params, "mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}


def adam_step(S, state, grads):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    if S["max_grad_norm"] > 0:
        clip = jnp.minimum(1.0, S["max_grad_norm"] / jnp.maximum(norm, 1e-30))
        grads = jax.tree.map(lambda g: g * jnp.where(norm < S["max_grad_norm"], 1.0, clip), grads)
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, state["nu"], grads)
    c1, c2 = 1 - ADAM_B1 ** count.astype(jnp.float32), 1 - ADAM_B2 ** count.astype(jnp.float32)
    params = jax.tree.map(lambda p, m, v: p - S["lr"] * (m / c1) / (jnp.sqrt(v / c2) + S["adam_eps"]), state["params"], mu, nu)
    return {"params": params, "mu": mu, "nu": nu, "count": count}


def constant_bias(grads):
    """The selection bias is no trained weight: whatever the differentiation gives it (the
    choice is piecewise constant in it: zero) is not a gradient."""
    return jax.tree_util.tree_map_with_path(lambda path, g: jnp.zeros_like(g) if str(path[-1].key) == "expert_bias" else g, grads)


def _tok(x):
    return jnp.asarray(x.T, jnp.int32)  # [T, N] -> [N, T]


def rollout_pass(S, params, context, roll, quant="f32"):
    """What the acting policy (``params``) said over one rollout of the environment's
    rows: log-probabilities of the actions taken and values ``[T, N]``, the values that
    bootstrap (each step's final observation, asked after it; the next observation, from
    where the rollout leaves every block's state) and the context with what the rollout's
    tokens left appended."""
    with jax.default_matmul_precision("highest"):
        flt = lambda x: jnp.asarray(x.T, jnp.float32)  # noqa: E731
        pos, ep = _tok(roll["pos"]), _tok(roll["ep"])
        side = (_tok(roll["final_obs"]), _tok(roll["action"]), pos + 1, ep)
        hidden, values, made, chosen, (_, after) = forward(S, params, context, _tok(roll["obs"]), _tok(roll["prev"]), flt(roll["is_first"]), pos, ep, quant, side)
        logp, _ = log_probs(S, params, hidden, _tok(roll["action"]), quant)
        grown = append(context, made, pos, ep)
        _, nxt, _, _, _ = forward(S, params, grown, _tok(roll["next_obs"]), _tok(roll["next_prev"]), flt(roll["next_is_first"]), _tok(roll["next_pos"]), _tok(roll["next_ep"]), quant)
        return {"logp": logp.T, "values": values.T, "after": after.T, "next_value": nxt[:, 0], "context": grown, "chosen": chosen}


def _rows(tree, idx, axis):
    return jax.tree.map(lambda v: jnp.take(v, idx, axis=axis) if getattr(v, "ndim", 0) > axis else v, tree)


def ppo_loss(S, params, context, roll, old, quant="f32"):
    with jax.default_matmul_precision("highest"):
        hidden, values, _, _, _ = forward(S, params, context, _tok(roll["obs"]), _tok(roll["prev"]), jnp.asarray(roll["is_first"].T, jnp.float32), _tok(roll["pos"]), _tok(roll["ep"]), quant)
        logp, entropy = log_probs(S, params, hidden, _tok(roll["action"]), quant)
        logp, entropy, values = logp.T, entropy.T, values.T
        ratio = jnp.exp(logp - old["logp"])
        adv = old["advantages"]
        policy = -jnp.mean(jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - S["clip_coef"], 1 + S["clip_coef"])))
        value = jnp.mean((values - old["returns"]) ** 2)
        ent = jnp.mean(entropy)
        total = policy + S["vf_coef"] * value - S["ent_coef"] * ent
        return total, {"policy": policy, "value": value, "entropy": ent}


def update(S, state, context, roll, quant="f32"):
    """One PPO update as the program makes it: the rollout's old log-probabilities, values
    and advantages from the acting weights, then ``update_epochs`` epochs of clipped Adam,
    one step a sequence minibatch: the envs of each row of ``roll["perm"]`` (``[epochs,
    minibatches, envs a minibatch]``, the program's own permutations), or all of them once an
    epoch where the roll has none.  Returns the new state, the grown context, the losses
    averaged over the steps, the per-token old log-probabilities and the experts chosen."""
    acting = rollout_pass(S, state["params"], context, roll, quant)
    with jax.default_matmul_precision("highest"):
        rewards = jnp.asarray(roll["reward"], jnp.float32) + S["gamma"] * acting["after"] * jnp.asarray(roll["truncated"], jnp.float32)
        returns, advantages = gae(S, rewards, acting["values"], jnp.asarray(roll["done"], jnp.float32), acting["next_value"])
    old = {"logp": acting["logp"], "returns": returns, "advantages": advantages}
    n = roll["obs"].shape[1]
    perm = roll.get("perm")
    perm = jnp.broadcast_to(jnp.arange(n), (S["update_epochs"], 1, n)) if perm is None else jnp.asarray(perm)
    steps = perm.reshape(-1, perm.shape[-1])
    data = {k: v for k, v in roll.items() if k != "perm"}

    def minibatch(state, idx):
        batch, mb_old, mb_context = _rows(data, idx, 1), _rows(old, idx, 1), _rows(context, idx, 0)
        (_, loss), grads = jax.value_and_grad(ppo_loss, argnums=1, has_aux=True)(S, state["params"], mb_context, batch, mb_old, quant)
        with jax.default_matmul_precision("highest"):
            return adam_step(S, state, constant_bias(grads)), loss

    state, losses = jax.lax.scan(minibatch, state, steps)
    return state, acting["context"], jax.tree.map(jnp.mean, losses), acting["logp"], acting["chosen"]
