"""``moonlight16b_1of8``'s plain reference: Moonlight-16B-A3B's forward pass (``deepseek_v3``:
latent attention, MLA, computed naively; a leading dense layer; a sigmoid router with a
selection bias and scaled weights; shared experts beside the routed ones; an untied head),
GAE, the clipped PPO loss and clipped Adam in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of ``sheeprl_tpu`` and
nothing of the other configurations' references.

The layer, as ``configs/moonlight16b_1of8.json`` states it (``assumed`` there lists what the
published config does not say).  Residual stream ``x``, ``H`` heads, layer ``l``:
``h = x + Attn(RMSNorm(x))``, ``out = h + FFN_l(RMSNorm(h))``.

* ``Attn(a)``: ``q = a W_q``, each head ``[q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]``;
  ``[c_raw, k_pe] = a W_kv_a``; ``c = RMSNorm(c_raw)`` over the ``kv_lora_rank`` dimensions;
  ``[k_nope_h, v_h] = split(c W_kv_b)`` for every head; RoPE (half-rotation) on every head's
  ``q_pe`` and on the one ``k_pe``, which all heads share; ``q_h = [q_nope_h, q_pe_h]``,
  ``k_h = [k_nope_h, k_pe]``; key ``j`` visible to query ``i`` iff same episode and ``pos_j <=
  pos_i``; scores over ``sqrt(qk_nope_head_dim + qk_rope_head_dim)``; ``y = concat_h(p v_h) W_o``;
* ``FFN_l``, ``l < dense_layers``: ``(silu(m W_1) * (m W_3)) W_2``;
* ``FFN_l`` otherwise: ``s = sigmoid(m W_r)`` over all the experts; the ``experts_per_token``
  with the largest ``s + b`` are chosen; their weights are ``s`` without ``b`` over (their sum
  + ``router_eps``), times ``routed_scale``; ``y = sum over the experts held, among the token's,
  of w_e (silu(m W_g^e) * (m W_u^e)) W_d^e``, plus the shared expert ``(silu(m S_g) * (m S_u))
  S_d`` for every token, unscaled.  ``b`` is a constant;
* after the layers: RMSNorm, logits ``hidden @ W_head`` over the rows held of the untied
  head, and a linear value head.  Input: ``E[token] + (1 - is_first) E[previous action]``.

MLA is computed **naively** here: every position's per-head keys and values are formed
from its ``c`` through ``W_kv_b``, sixteen heads of ``128 + 64`` and ``128``, and attended as any
attention is; nothing is folded into the queries or the output, and nothing is attended in
the latent's space.  There is no cache and no slot: every token of an env so far is a row of
plain arrays in the order it came (room for the rollouts followed is reserved at the start,
rows not yet written belong to no episode), each with its episode and its position; the
mask is made from those.  What the algorithm itself carries is carried: recurrent PPO keeps
the context of earlier rollouts as a constant of the update, and what an MLA layer keeps of
a token is its normed latent ``c`` and its rotated ``k_pe``, 576 numbers, as the weights of
their time produced them, appended after each rollout's forward pass, never recomputed
(``stop_gradient`` says so where they are read).  ``W_kv_b`` is a weight of the update
wherever it multiplies: the earlier tokens' keys and values are formed from their constant
``c`` with the weights being trained, so ``W_kv_b`` takes gradient through them too.  Every
expert held is computed densely for every token and weighted by its routing weight (zero
where the token did not choose it).

``quant``: ``"f32"`` is the reference; ``"bf16"`` / ``"fp8"`` round every matmul operand of the
model to that precision first (the router stays float32, as stated): the controls of the
comparison that decides ``correct``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2 = 0.9, 0.999


# --------------------------------------------------------------------------- weights
def layer_shapes(S: Dict[str, Any], l: int) -> Dict[str, Tuple[int, ...]]:
    D, H, r = S["hidden_size"], S["heads_held"], S["kv_lora_rank"]
    dn, dr, dv = S["qk_nope_head_dim"], S["qk_rope_head_dim"], S["v_head_dim"]
    out: Dict[str, Tuple[int, ...]] = {
        "attn_norm": (D,), "wq": (D, H * (dn + dr)), "wkv_a": (D, r + dr), "kv_norm": (r,), "wkv_b": (r, H * (dn + dv)), "wo": (H * dv, D), "ffn_norm": (D,),
    }  # fmt: skip
    if l < S["dense_layers"]:
        F = S["dense_width"]
        out.update(dense_gate=(D, F), dense_up=(D, F), dense_down=(F, D))
    else:
        E, F, Fs = S["experts_held"], S["expert_width"], S["shared_width"]
        out.update(router=(D, S["num_experts"]), expert_bias=(S["num_experts"],), w_gate=(E, D, F), w_up=(E, D, F), w_down=(E, F, D))
        out.update(shared_gate=(D, Fs), shared_up=(D, Fs), shared_down=(Fs, D))
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
    1 / fan-in: a head's queries and keys then have unit variance a dimension, and a score
    (192 products over ``sqrt(192)``) is of order one, so the softmax over a cache is not
    flat and a key left out or misplaced shows.  The branches' output projections (``wo``,
    the dense, routed and shared down-projections) are scaled by ``S["branch_scale"]`` and
    the router by ``S["router_scale"]``, so that the stream is mostly the exact embedding
    sum and few of the top-k choices sit on a tie that bf16 rounding flips.  The embedding
    has unit variance; the head is normal with variance 1 / D and reads a normed state:
    logits of order one.  ``expert_bias`` is normal with deviation ``S["bias_scale"]``: wide
    enough to change the chosen set for about a tenth of the tokens
    (``MoE/bias_moved_share`` reads the share), a constant of the run."""
    flat, treedef = _flat(S)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    leaves = []
    for (path, shape), key in zip(flat, keys):
        name = str(path[-1].key)
        if name.endswith("norm"):
            w = 1.0 + 0.1 * jax.random.normal(key, shape)
        elif name == "value_b":
            w = jnp.zeros(shape)
        elif name == "embed":
            w = jax.random.normal(key, shape)
        elif name == "expert_bias":
            w = S["bias_scale"] * jax.random.normal(key, shape)
        else:
            scale = {"router": S["router_scale"], "value_w": 0.5}.get(name, S["branch_scale"] if name in ("wo", "w_down", "dense_down", "shared_down") else 1.0)
            w = jax.random.normal(key, shape) * (scale / np.sqrt(shape[-2]))
        leaves.append(w.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_groups(S: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Leaves of ``jax.tree.leaves(params)`` pooled by kind for ``grad_gap.<group>``."""
    names = [str(p[-1].key) for p, _ in _flat(S)[0]]
    kinds = {
        "attention": ("wq", "wkv_a", "wkv_b", "wo", "kv_norm"),
        "shared": ("shared_gate", "shared_up", "shared_down"),
        "experts": ("w_gate", "w_up", "w_down"),
        "router": ("router",),
        "dense": ("dense_gate", "dense_up", "dense_down"),
        "tables": ("embed", "head"),
    }
    return {g: {"leaves": [i for i, n in enumerate(names) if n in ks], "by": "pooled"} for g, ks in kinds.items()}


# --------------------------------------------------------------------------- the model
def _rounder(quant: str):
    if quant == "f32":
        return lambda x: x
    dtype = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[quant]
    # the value rounded, the gradient passed straight through (a cast to fp8 alone stops it)
    return lambda x: x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """``x``: ``[N, T, H, d]``, all ``d`` dimensions rotated; first half and second half rotate together."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, :, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle), x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def empty_context(S: Dict[str, Any], n: int, room: int) -> Dict[str, Any]:
    """Room for ``room`` tokens an env; a row not yet written has episode ``-1``, which no
    token is of (the first episode is 1).  A layer keeps of a token its normed latent and
    its rotated shared key."""
    layers = [{"c": jnp.zeros((n, room, S["kv_lora_rank"]), jnp.float32), "k_pe": jnp.zeros((n, room, S["qk_rope_head_dim"]), jnp.float32)} for _ in range(S["layers"])]
    return {"layers": layers, "pos": jnp.zeros((n, room), jnp.int32), "ep": jnp.full((n, room), -1, jnp.int32), "filled": jnp.zeros((), jnp.int32)}


def append(context: Dict[str, Any], made, pos, ep) -> Dict[str, Any]:
    at, put = context["filled"], jax.lax.dynamic_update_slice_in_dim
    layers = [{name: put(c[name], m[name], at, 1) for name in c} for c, m in zip(context["layers"], made)]
    return {"layers": layers, "pos": put(context["pos"], pos, at, 1), "ep": put(context["ep"], ep, at, 1), "filled": at + pos.shape[1]}


def latent_attention(S, L, x, ctx, context, pos, ep, R, isolated):
    """Naive MLA over the context's rows and the chunk's own: per-head keys and values of
    every row from its latent through ``W_kv_b``."""
    N, T, _ = x.shape
    H, r = S["heads_held"], S["kv_lora_rank"]
    dn, dr, dv = S["qk_nope_head_dim"], S["qk_rope_head_dim"], S["v_head_dim"]
    a = R(rms_norm(x, L["attn_norm"], S["norm_eps"]))
    q = (a @ R(L["wq"])).reshape(N, T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, S["rope_theta"])], -1)
    down = a @ R(L["wkv_a"])
    c = rms_norm(down[..., :r], L["kv_norm"], S["norm_eps"])
    k_pe = rope(down[:, :, None, r:], pos, S["rope_theta"])[:, :, 0]
    # the earlier rollouts' rows are constants of the update; the weights that read them are not
    c_all = jnp.concatenate([jax.lax.stop_gradient(ctx["c"]), c], 1)  # [N, C + T, r]
    k_pe_all = jnp.concatenate([jax.lax.stop_gradient(ctx["k_pe"]), k_pe], 1)  # [N, C + T, dr]
    up = (R(c_all) @ R(L["wkv_b"])).reshape(N, -1, H, dn + dv)
    keys = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_pe_all[:, :, None, :], (*up.shape[:3], dr))], -1)
    vals = up[..., dn:]
    k_pos, k_ep = jnp.concatenate([context["pos"], pos], 1), jnp.concatenate([context["ep"], ep], 1)
    see = (k_ep[:, None, :] == ep[:, :, None]) & (k_pos[:, None, :] <= pos[:, :, None])
    if isolated:  # of the chunk a token reads itself alone
        C = context["pos"].shape[1]
        see = see.at[:, :, C:].set(jnp.broadcast_to(jnp.eye(T, dtype=bool), (N, T, T)))
    s = jnp.einsum("nthd,nchd->nhtc", R(q), R(keys)) / np.sqrt(dn + dr)
    w = jax.nn.softmax(jnp.where(see[:, None], s, -jnp.inf), -1)
    o = jnp.einsum("nhtc,nchd->nthd", R(w), R(vals)).reshape(N, T, H * dv)
    return x + R(o) @ R(L["wo"]), {"c": c, "k_pe": k_pe}


def layer(S, l, L, x, context, pos, ep, quant="f32", isolated=False):
    """One layer over a chunk ``x``: ``[N, T, D]`` (``L``: its weights) -> its output, what
    the chunk adds to the context, and the experts each token chose ``[N, T, k]``
    (``None`` for a dense layer)."""
    R = _rounder(quant)
    h, made = latent_attention(S, L, x, context["layers"][l], context, pos, ep, R, isolated)
    normed = rms_norm(h, L["ffn_norm"], S["norm_eps"])
    m = R(normed)
    if l < S["dense_layers"]:
        return h + R(jax.nn.silu(m @ R(L["dense_gate"])) * (m @ R(L["dense_up"]))) @ R(L["dense_down"]), made, None

    score = jax.nn.sigmoid(normed @ L["router"])
    _, top_i = jax.lax.top_k(score + L["expert_bias"], S["experts_per_token"])
    top_w = jnp.take_along_axis(score, top_i, -1)
    if S.get("norm_topk_prob", True):
        top_w = top_w / (top_w.sum(-1, keepdims=True) + S["router_eps"])
    top_w = top_w * S["routed_scale"]

    def one_expert(y, e_w):
        e, wg, wu, wd = e_w
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)  # 0 where the token did not choose e
        act = jax.nn.silu(m @ R(wg)) * (m @ R(wu))
        return y + weight[..., None] * (R(act) @ R(wd)), None

    held = S.get("expert_offset", 0) + jnp.arange(S["experts_held"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (held, L["w_gate"], L["w_up"], L["w_down"]))
    if S.get("shared_here", True):  # every chip of the group computes the shared expert alike: a test that adds the shares up counts it once
        y = y + R(jax.nn.silu(m @ R(L["shared_gate"])) * (m @ R(L["shared_up"]))) @ R(L["shared_down"])
    return h + y, made, top_i


def forward(S, params, context, tokens, prev, is_first, pos, ep, quant="f32", isolated=False):
    """``tokens, prev, is_first, pos, ep``: ``[N, T]``; ``context``: what every earlier
    token of each env left (per layer), with its position and episode.  Returns the final
    normed hidden state ``[N, T, D]``, the values ``[N, T]``, what the chunk adds to the
    context per layer, and the experts chosen ``[expert layers, N, T, k]``.  ``isolated``:
    the chunk's tokens do not see one another (each is a question asked of the context)."""
    P = params["params"]
    x = P["embed"][tokens] + (1.0 - is_first)[..., None] * P["embed"][prev]
    made, chosen = [], []
    for l in range(S["layers"]):
        x, new, top_i = layer(S, l, P[f"layers_{l}"], x, context, pos, ep, quant, isolated)
        made.append(new)
        if top_i is not None:
            chosen.append(top_i)
    hidden = rms_norm(x, P["final_norm"], S["norm_eps"])
    values = (hidden @ P["value_w"] + P["value_b"])[..., 0]
    none = jnp.zeros((0, *tokens.shape, S["experts_per_token"]), jnp.int32)  # a model of dense layers alone
    return hidden, values, made, jnp.stack(chosen) if chosen else none


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


def rollout_pass(S, params, context, roll, quant="f32"):
    """What the acting policy (``params``) said over one rollout of the environment's
    rows: log-probabilities of the actions taken and values ``[T, N]``, the values that
    bootstrap (the next observation's; a truncated episode's last observation's) and
    the context with what the rollout's tokens left appended."""
    with jax.default_matmul_precision("highest"):
        tok = lambda x: jnp.asarray(x.T, jnp.int32)  # noqa: E731  [T, N] -> [N, T]
        flt = lambda x: jnp.asarray(x.T, jnp.float32)  # noqa: E731
        hidden, values, made, chosen = forward(S, params, context, tok(roll["obs"]), tok(roll["prev"]), flt(roll["is_first"]), tok(roll["pos"]), tok(roll["ep"]), quant)
        logp, _ = log_probs(S, params, hidden, tok(roll["action"]), quant)
        grown = append(context, made, tok(roll["pos"]), tok(roll["ep"]))
        # each step's "what if the episode went on": the observation that followed it, asked of the context up to it
        _, after, _, _ = forward(S, params, grown, tok(roll["final_obs"]), tok(roll["action"]), jnp.zeros_like(flt(roll["is_first"])), tok(roll["pos"]) + 1, tok(roll["ep"]), quant, isolated=True)
        _, nxt, _, _ = forward(S, params, grown, tok(roll["next_obs"]), tok(roll["next_prev"]), flt(roll["next_is_first"]), tok(roll["next_pos"]), tok(roll["next_ep"]), quant, isolated=True)
        return {"logp": logp.T, "values": values.T, "after": after.T, "next_value": nxt[:, 0], "context": grown, "chosen": chosen}


def ppo_loss(S, params, context, roll, old, quant="f32"):
    with jax.default_matmul_precision("highest"):
        tok = lambda x: jnp.asarray(x.T, jnp.int32)  # noqa: E731
        hidden, values, _, _ = forward(S, params, context, tok(roll["obs"]), tok(roll["prev"]), jnp.asarray(roll["is_first"].T, jnp.float32), tok(roll["pos"]), tok(roll["ep"]), quant)
        logp, entropy = log_probs(S, params, hidden, tok(roll["action"]), quant)
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
    and advantages from the acting weights, then ``update_epochs`` steps of clipped Adam
    over the whole rollout (one minibatch an epoch).  Returns the new state, the grown
    context, the losses averaged over the epochs, the per-token old log-probabilities and
    the experts chosen."""
    acting = rollout_pass(S, state["params"], context, roll, quant)
    with jax.default_matmul_precision("highest"):
        rewards = jnp.asarray(roll["reward"], jnp.float32) + S["gamma"] * acting["after"] * jnp.asarray(roll["truncated"], jnp.float32)
        returns, advantages = gae(S, rewards, acting["values"], jnp.asarray(roll["done"], jnp.float32), acting["next_value"])
    old = {"logp": acting["logp"], "returns": returns, "advantages": advantages}

    def epoch(state, _):
        (_, loss), grads = jax.value_and_grad(ppo_loss, argnums=1, has_aux=True)(S, state["params"], context, roll, old, quant)
        with jax.default_matmul_precision("highest"):
            return adam_step(S, state, constant_bias(grads)), loss

    state, losses = jax.lax.scan(epoch, state, None, length=S["update_epochs"])
    return state, acting["context"], jax.tree.map(jnp.mean, losses), acting["logp"], acting["chosen"]
