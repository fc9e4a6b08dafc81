"""``smallthinker21b_1of4``'s plain reference: the decoder's forward pass, GAE, the clipped
PPO loss and clipped Adam in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of ``sheeprl_tpu``.

The layer, as ``configs/smallthinker21b_1of4.json`` states it (``assumed`` there lists
what the published config does not say).  Residual stream ``x``, layer ``l``:

* router: ``p = softmax(x W_r)`` over all the experts, read from the layer's input; the
  ``experts_per_token`` largest, weights renormalised over them;
* attention: ``a = RMSNorm(x)``; ``q, k, v = a W_q, a W_k, a W_v`` (grouped heads, no bias);
  RoPE (half-rotation, theta over the whole head) where ``rope_layout[l]``; key ``j``
  visible to query ``i`` iff same episode, ``pos_j <= pos_i`` and, where
  ``window_layout[l]``, ``pos_i - pos_j < window``; scores over ``sqrt(head_dim)``;
  ``h = x + concat(heads) W_o``;
* experts: ``m = RMSNorm(h)``; ``y = sum over the experts held, among the token's, of
  w_e (relu(m W_g^e) * (m W_u^e)) W_d^e``; ``out = h + y``;
* after the layers: RMSNorm, logits over the rows held of the untied head, and a linear
  value head.  Input: ``E[token] + (1 - is_first) E[previous action]``.

There is no cache here, no ring and no slot: every token of an env so far is a row of
plain arrays in the order it came (room for the rollouts followed is reserved at the
start, rows not yet written belong to no episode), each with its episode and its
position, and the masks are made from those.  What the algorithm itself carries is
carried: recurrent PPO keeps the context of earlier rollouts as a constant of the update (as it keeps an LSTM's
``c0, h0``), so the keys and values of earlier rollouts are the ones the weights of
their time produced, appended after each rollout's forward pass, never recomputed.
Every expert held is computed densely for every token and weighted by its routing
weight (zero where the token did not choose it): no sorting, no grouping.

``quant``: ``"f32"`` is the reference; ``"bf16"`` / ``"fp8"`` round every matmul operand
of the model to that precision first (the router stays float32, as stated): the
controls of the comparison that decides ``correct``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2 = 0.9, 0.999


# --------------------------------------------------------------------------- weights
def layer_shapes(S: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    D, hd, E, F = S["hidden_size"], S["head_dim"], S["experts_held"], S["expert_width"]
    return {
        "attn_norm": (D,),
        "ffn_norm": (D,),
        "router": (D, S["num_experts"]),
        "w_down": (E, F, D),
        "w_gate": (E, D, F),
        "w_up": (E, D, F),
        "wk": (D, S["kv_heads_held"] * hd),
        "wo": (S["heads_held"] * hd, D),
        "wq": (D, S["heads_held"] * hd),
        "wv": (D, S["kv_heads_held"] * hd),
    }


def shapes(S: Dict[str, Any]) -> Dict[str, Any]:
    D, V = S["hidden_size"], S["vocab_held"]
    tree: Dict[str, Any] = {"embed": (V, D), "final_norm": (D,), "head": (D, V), "value_b": (1,), "value_w": (D, 1)}
    for i in range(S["layers"]):
        tree[f"layers_{i}"] = layer_shapes(S)
    return {"params": tree}


def flat_shapes(S: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    flat = jax.tree_util.tree_flatten_with_path(shapes(S), is_leaf=lambda x: isinstance(x, tuple))[0]
    return {"/".join(str(k.key) for k in path): shape for path, shape in flat}


def make_weights(S: Dict[str, Any], seed) -> Dict[str, Any]:
    """The benchmark's weights from the seed.  Matmul weights are normal with variance
    1 / fan-in, so activations keep their scale through the layers; the two branches'
    output projections are scaled by ``S["branch_scale"]`` and the router by
    ``S["router_scale"]``: with small branches the router's input is mostly the exact
    embedding sum, and a wide router spreads its logits, so few of the top-k choices sit
    on a tie that bf16 rounding flips (PERF.md says how many still do)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes(S), is_leaf=lambda x: isinstance(x, tuple))
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
        else:
            fan_in = shape[-2]
            scale = {"router": S["router_scale"], "wo": S["branch_scale"], "w_down": S["branch_scale"], "value_w": 0.5}.get(name, 1.0)
            w = jax.random.normal(key, shape) * (scale / np.sqrt(fan_in))
        leaves.append(w.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_groups(S: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Leaves of ``jax.tree.leaves(params)`` pooled by kind for ``grad_gap.<group>``."""
    names = [str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(shapes(S), is_leaf=lambda x: isinstance(x, tuple))[0]]
    kinds = {
        "experts": ("w_gate", "w_up", "w_down"),
        "router": ("router",),
        "attention": ("wq", "wk", "wv", "wo"),
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
    """``x``: ``[N, T, H, hd]``; first half and second half of a head rotate together."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, :, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle), x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def empty_context(S: Dict[str, Any], n: int, room: int) -> Dict[str, Any]:
    """Room for ``room`` tokens an env; a row not yet written has episode ``-1``, which no
    token is of (the first episode is 1)."""
    kv = lambda: jnp.zeros((n, room, S["kv_heads_held"], S["head_dim"]), jnp.float32)  # noqa: E731
    layers = [{"k": kv(), "v": kv()} for _ in range(S["layers"])]
    return {"layers": layers, "pos": jnp.zeros((n, room), jnp.int32), "ep": jnp.full((n, room), -1, jnp.int32), "filled": jnp.zeros((), jnp.int32)}


def append(context: Dict[str, Any], made, pos, ep) -> Dict[str, Any]:
    at, put = context["filled"], jax.lax.dynamic_update_slice_in_dim
    layers = [{"k": put(c["k"], m["k"], at, 1), "v": put(c["v"], m["v"], at, 1)} for c, m in zip(context["layers"], made)]
    return {"layers": layers, "pos": put(context["pos"], pos, at, 1), "ep": put(context["ep"], ep, at, 1), "filled": at + pos.shape[1]}


def layer(S, l, L, x, context, pos, ep, quant="f32", isolated=False):
    """One layer over a chunk ``x``: ``[N, T, D]`` (``L``: its weights) -> its output, the
    chunk's keys and values, and the experts each token chose ``[N, T, k]``."""
    R = _rounder(quant)
    N, T, _ = x.shape
    hd, Hq, Hkv, K = S["head_dim"], S["heads_held"], S["kv_heads_held"], S["experts_per_token"]
    probs = jax.nn.softmax(x @ L["router"], -1)
    top_p, top_i = jax.lax.top_k(probs, K)
    top_w = top_p / top_p.sum(-1, keepdims=True) if S.get("norm_topk_prob", True) else top_p

    a = R(rms_norm(x, L["attn_norm"], S["rms_norm_eps"]))
    q = (a @ R(L["wq"])).reshape(N, T, Hq, hd)
    k = (a @ R(L["wk"])).reshape(N, T, Hkv, hd)
    v = (a @ R(L["wv"])).reshape(N, T, Hkv, hd)
    if S["rope_layout"][l]:
        q, k = rope(q, pos, S["rope_theta"]), rope(k, pos, S["rope_theta"])
    ctx = context["layers"][l]
    keys, vals = jnp.concatenate([ctx["k"], k], 1), jnp.concatenate([ctx["v"], v], 1)
    k_pos, k_ep = jnp.concatenate([context["pos"], pos], 1), jnp.concatenate([context["ep"], ep], 1)
    see = (k_ep[:, None, :] == ep[:, :, None]) & (k_pos[:, None, :] <= pos[:, :, None])
    if S["window_layout"][l]:
        see = see & (pos[:, :, None] - k_pos[:, None, :] < S["window"])
    if isolated:
        C = context["pos"].shape[1]
        see = see.at[:, :, C:].set(jnp.broadcast_to(jnp.eye(T, dtype=bool), (N, T, T)))
    qg = R(q).reshape(N, T, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("nthgd,nchd->nhgtc", qg, R(keys)) / np.sqrt(hd)
    w = jax.nn.softmax(jnp.where(see[:, None, None], s, -jnp.inf), -1)
    o = jnp.einsum("nhgtc,nchd->nthgd", R(w), R(vals)).reshape(N, T, Hq * hd)
    h = x + R(o) @ R(L["wo"])

    m = R(rms_norm(h, L["ffn_norm"], S["rms_norm_eps"]))

    def one_expert(y, e_w):
        e, wg, wu, wd = e_w
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)  # 0 where the token did not choose e
        act = jax.nn.relu(m @ R(wg)) * (m @ R(wu))
        return y + weight[..., None] * (R(act) @ R(wd)), None

    held = S.get("expert_offset", 0) + jnp.arange(S["experts_held"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (held, L["w_gate"], L["w_up"], L["w_down"]))
    return h + y, {"k": k, "v": v}, top_i


def forward(S, params, context, tokens, prev, is_first, pos, ep, quant="f32", isolated=False):
    """``tokens, prev, is_first, pos, ep``: ``[N, T]``; ``context``: the keys and values
    (per layer), positions and episodes of every earlier token of each env.  Returns the
    final normed hidden state ``[N, T, D]``, the values ``[N, T]``, the chunk's keys and
    values per layer, and the experts chosen ``[layers, N, T, k]``.  ``isolated``: the
    chunk's tokens do not see one another (each is a question asked of the context)."""
    P = params["params"]
    x = P["embed"][tokens] + (1.0 - is_first)[..., None] * P["embed"][prev]
    made, chosen = [], []
    for l in range(S["layers"]):
        x, kv, top_i = layer(S, l, P[f"layers_{l}"], x, context, pos, ep, quant, isolated)
        made.append(kv)
        chosen.append(top_i)
    hidden = rms_norm(x, P["final_norm"], S["rms_norm_eps"])
    values = (hidden @ P["value_w"] + P["value_b"])[..., 0]
    return hidden, values, made, jnp.stack(chosen)


def log_probs(S, params, hidden, actions, quant="f32", block=512):
    """Log-probability of ``actions`` and the entropy, ``[N, T]``, under the softmax of the
    head's logits; formed ``block`` tokens at a time so that the whole fits beside the
    optimizer's state (plain arithmetic, blocked)."""
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
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params), "count": jnp.zeros((), jnp.int32)}


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


def rollout_pass(S, params, context, roll, quant="f32"):
    """What the acting policy (``params``) said over one rollout of the environment's
    rows: log-probabilities of the actions taken and values ``[T, N]``, the values that
    bootstrap (the next observation's; a truncated episode's last observation's) and
    the context with the rollout's keys and values appended."""
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
    context, the losses averaged over the epochs, and the per-token old log-probabilities."""
    acting = rollout_pass(S, state["params"], context, roll, quant)
    with jax.default_matmul_precision("highest"):
        rewards = jnp.asarray(roll["reward"], jnp.float32) + S["gamma"] * acting["after"] * jnp.asarray(roll["truncated"], jnp.float32)
        returns, advantages = gae(S, rewards, acting["values"], jnp.asarray(roll["done"], jnp.float32), acting["next_value"])
    old = {"logp": acting["logp"], "returns": returns, "advantages": advantages}

    def epoch(state, _):
        (_, loss), grads = jax.value_and_grad(ppo_loss, argnums=1, has_aux=True)(S, state["params"], context, roll, old, quant)
        with jax.default_matmul_precision("highest"):
            return adam_step(S, state, grads), loss

    state, losses = jax.lax.scan(epoch, state, None, length=S["update_epochs"])
    return state, acting["context"], jax.tree.map(jnp.mean, losses), acting["logp"], acting["chosen"]
