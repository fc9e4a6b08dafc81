"""Plain float32 reference of one DreamerV3 gradient step (Hafner et al. 2023).

Straight ``jax.numpy``: no flax, no optax, no kernels, nothing imported from the
program.  It is the yardstick ``correct`` is decided by: the harness hands it the
weights the benchmark made from the seed, the rows the benchmark's own environment
emitted, and the indices and PRNG key the program drew, and it recomputes the first
gradient steps (world-model loss through encoder, RSSM scan with ``is_first``
resets, decoder, reward and continue heads and the balanced KL with free nats; the
15-step imagination, lambda-returns, percentile moments, actor and critic losses;
three clipped Adam updates and the target-critic EMA).

The parameter tree is laid out under the names the published sheeprl agent uses
(``world_model/params/encoder/cnn_encoder/Conv_0/kernel`` ...), so the same tree can
be handed to the program.  :func:`param_shapes` builds that layout from the
configuration's sizes alone; the harness refuses a program whose tree differs.

Departures from the paper that follow the program, noted because the comparison
needs them: every encoder vector key is also reconstructed (the exp's decoder list is
not consulted); Adam's ``b2`` is 0.999 whatever ``betas[1]`` says; categorical samples
are Gumbel-argmax draws of ``jax.random.categorical`` with the key schedule below.

``quant`` selects the precision of every matmul and convolution *input*:
``"f32"`` (the reference, ``Precision.HIGHEST``), ``"bf16"`` (what the
configuration states for compute) and ``"fp8"`` (e4m3, the control: the nearest
precision below the stated one).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-3
ADAM_B1, ADAM_B2 = 0.9, 0.999


# --------------------------------------------------------------------------- shapes
def _mlp_shapes(in_dim: int, units: int, layers: int) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    d = in_dim
    for i in range(layers):
        out[f"Dense_{i}"] = {"kernel": (d, units), "bias": (units,)}
        out[f"LayerNorm_{i}"] = {"scale": (units,), "bias": (units,)}
        d = units
    return out


def latent_size(S: Dict[str, Any]) -> int:
    return S["stochastic_size"] * S["discrete_size"] + S["recurrent_state_size"]


def param_shapes(S: Dict[str, Any]) -> Dict[str, Any]:
    """The whole agent's parameter tree as nested dicts of shapes."""
    m, units, layers = S["cnn_channels_multiplier"], S["dense_units"], S["mlp_layers"]
    stoch = S["stochastic_size"] * S["discrete_size"]
    rec, lat, A = S["recurrent_state_size"], latent_size(S), S["actions"]
    C, vec = S["image_channels"], S["vector_obs_dim"]
    enc_c = [m * 2**i for i in range(4)]
    cnn_enc: Dict[str, Any] = {}
    cin = C
    for i, c in enumerate(enc_c):
        cnn_enc[f"Conv_{i}"] = {"kernel": (4, 4, cin, c)}
        cnn_enc[f"LayerNorm_{i}"] = {"scale": (c,), "bias": (c,)}
        cin = c
    embed = enc_c[-1] * 16 + units
    dec: Dict[str, Any] = {"latent_proj": {"kernel": (lat, 16 * enc_c[-1]), "bias": (16 * enc_c[-1],)}}
    cin = enc_c[-1]
    for j, c in enumerate(reversed(enc_c[:-1])):
        dec[f"ConvTranspose_{j}"] = {"kernel": (4, 4, cin, c)}
        dec[f"LayerNorm_{j}"] = {"scale": (c,), "bias": (c,)}
        cin = c
    dec["head"] = {"kernel": (4, 4, cin, C), "bias": (C,)}
    head_mlp = lambda: {"layers_0": _mlp_shapes(lat, units, layers)}  # noqa: E731
    wm = {
        "encoder": {"cnn_encoder": cnn_enc, "mlp_encoder": {"MLP_0": _mlp_shapes(vec, units, layers)}},
        "rssm": {
            "initial_recurrent_state": (rec,),
            "recurrent_model": {
                "input_proj": _mlp_shapes(stoch + A, units, 1),
                "rnn": {"Dense_0": {"kernel": (units + rec, 3 * rec)}, "ln_scale": (3 * rec,), "ln_bias": (3 * rec,)},
            },
            "representation_model": {"layers_0": _mlp_shapes(rec + embed, S["representation_hidden_size"], 1)},
            "repr_logits": {"kernel": (S["representation_hidden_size"], stoch), "bias": (stoch,)},
            "transition_model": {"layers_0": _mlp_shapes(rec, S["transition_hidden_size"], 1)},
            "trans_logits": {"kernel": (S["transition_hidden_size"], stoch), "bias": (stoch,)},
        },
        "observation_model_cnn": dec,
        "observation_model_mlp": {
            "MLP_0": _mlp_shapes(lat, units, layers),
            "head_reward": {"kernel": (units, vec), "bias": (vec,)},
        },
        "reward_model": head_mlp(),
        "reward_head": {"kernel": (units, S["reward_bins"]), "bias": (S["reward_bins"],)},
        "continue_model": head_mlp(),
        "continue_head": {"kernel": (units, 1), "bias": (1,)},
    }
    actor = {"MLP_0": _mlp_shapes(lat, units, layers), "head_0": {"kernel": (units, A), "bias": (A,)}}
    critic = {
        "MLP_0": _mlp_shapes(lat, units, layers),
        "head": {"kernel": (units, S["critic_bins"]), "bias": (S["critic_bins"],)},
    }
    return {
        "world_model": {"params": wm},
        "actor": {"params": actor},
        "critic": {"params": critic},
        "target_critic": {"params": jax.tree.map(lambda s: s, critic, is_leaf=lambda x: isinstance(x, tuple))},
    }


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


def flat_shapes(S: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """``{"world_model/params/.../kernel": shape}`` in sorted order."""
    leaves = jax.tree_util.tree_flatten_with_path(param_shapes(S), is_leaf=_is_shape)[0]
    return {"/".join(str(k.key) for k in path): shape for path, shape in leaves}


def leaf_groups(S: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Groups of leaves whose gradient is compared as a group, as indices into the trained
    trees' leaves (``actor``, ``critic``, ``world_model`` in ``jax.tree.leaves`` order).
    ``transition``, by its worst leaf: the prior's layers, whose only gradient is the
    dynamic KL term's: their gradient norm scales with ``kl_dynamic`` and vanishes under a
    stop-gradient on the prior.  ``world_model``, pooled: the whole tree that the sampled
    posterior feeds; its large leaves (decoder, GRU) sum over every frame of the batch,
    so a few flipped draws hardly move their norms and a lower precision does."""
    trained = [k for k in flat_shapes(S) if not k.startswith("target_critic/")]
    transition = [i for i, k in enumerate(trained) if "/transition_model/" in k or "/trans_logits/" in k]
    world_model = [i for i, k in enumerate(trained) if k.startswith("world_model/")]
    return {
        "transition": {"leaves": transition, "by": "worst"},
        "world_model": {"leaves": world_model, "by": "pooled"},
    }


#: the layers whose outputs are the logits of a categorical that is sampled
LOGIT_HEADS = ("trans_logits", "repr_logits", "head_0")
LOGIT_HEAD_SCALE = 0.02
#: added to the posterior head's bias on half of each categorical's classes
POSTERIOR_FLOOR = -8.0


def make_weights(S: Dict[str, Any], seed: jax.Array) -> Dict[str, Any]:
    """The benchmark's weights, a function of the seed alone (one jitted call on the
    device).  Matrices and filters ~ N(0, 1/fan_in); norm scales 1 + 0.1 N; biases and
    the initial recurrent state 0.02 N; the target critic starts as the critic.

    The three heads whose outputs are sampled (prior, posterior, policy) are scaled by
    ``LOGIT_HEAD_SCALE``: their categoricals start near uniform, as in early training,
    so a draw is decided by the key's Gumbel noise and almost never by the last bits of
    a logit.  With logits of order one, a rounding of 1e-2 flips about one draw in a
    hundred, ~300 of a step's 32,768, and those flips, not the arithmetic, then set
    every gap between the program and the reference (PERF.md, PR 24).

    The posterior head's bias rules out half of each categorical's classes (which half
    is the seed's): ``POSTERIOR_FLOOR`` on their logits leaves them the unimix floor and
    nothing else.  The posterior is then near uniform over 16 of 32 classes and the prior
    over all 32, a KL of ~0.67 nats a categorical, ~21 nats a state: well above the free
    nats, so both KL terms carry gradient (the dynamic one is the transition model's
    only gradient).  A ruled-out class all but never wins a draw, so the rounding of its
    large logit decides none."""
    shapes = param_shapes(S)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    base = jax.random.fold_in(jax.random.PRNGKey(20240924), seed)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = str(path[-1].key)
        k = jax.random.fold_in(base, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "kernel":
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            scale = LOGIT_HEAD_SCALE if str(path[-2].key) in LOGIT_HEADS else 1.0
            out.append(z * (scale / jnp.sqrt(float(fan_in))))
        elif name in ("scale", "ln_scale"):
            out.append(1.0 + 0.1 * z)
        elif name == "bias" and str(path[-2].key) == "repr_logits":
            by_class = z.reshape(S["stochastic_size"], S["discrete_size"])
            rank = jnp.argsort(jnp.argsort(by_class, -1), -1)
            out.append(0.02 * z + POSTERIOR_FLOOR * (rank < S["discrete_size"] // 2).reshape(shape))
        else:
            out.append(0.02 * z)
    params = jax.tree_util.tree_unflatten(treedef, out)
    params["target_critic"] = jax.tree.map(lambda x: x, params["critic"])
    return params


# --------------------------------------------------------------------------- layers
def _quantizer(quant: str):
    if quant == "f32":
        return lambda x: x
    if quant == "bf16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        return lambda x: jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown precision {quant!r}")


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def silu(x):
    return x * jax.nn.sigmoid(x)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


class Net:
    """The layer functions at one precision."""

    def __init__(self, S: Dict[str, Any], quant: str = "f32"):
        self.S = S
        self.q = _quantizer(quant)

    def dense(self, p, x):
        y = jnp.dot(self.q(x), self.q(p["kernel"]), precision=HI)
        return y + p["bias"] if "bias" in p else y

    def mlp(self, p, x, layers):
        for i in range(layers):
            x = silu(layer_norm(self.dense(p[f"Dense_{i}"], x), **p[f"LayerNorm_{i}"]))
        return x

    def conv(self, kernel, x):
        return jax.lax.conv_general_dilated(
            self.q(x), self.q(kernel), (2, 2), ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI
        )

    def deconv(self, kernel, x):
        return jax.lax.conv_transpose(
            self.q(x), self.q(kernel), (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI
        )

    # ---- world model parts
    def encode(self, wm, rgb_u8, vec):
        """rgb_u8 [..., C, H, W] uint8, vec [..., V] -> [..., E]."""
        lead = rgb_u8.shape[:-3]
        x = rgb_u8.astype(jnp.float32) / 255.0 - 0.5
        x = jnp.moveaxis(x, -3, -1).reshape(-1, *rgb_u8.shape[-2:], rgb_u8.shape[-3])
        p = wm["encoder"]["cnn_encoder"]
        for i in range(4):
            x = silu(layer_norm(self.conv(p[f"Conv_{i}"]["kernel"], x), **p[f"LayerNorm_{i}"]))
        cnn = x.reshape(*lead, -1)
        v = self.mlp(wm["encoder"]["mlp_encoder"]["MLP_0"], symlog(vec), self.S["mlp_layers"])
        return jnp.concatenate([cnn, v], -1)

    def decode_rgb(self, wm, latent):
        p = wm["observation_model_cnn"]
        lead = latent.shape[:-1]
        x = self.dense(p["latent_proj"], latent).reshape(-1, 4, 4, p["latent_proj"]["kernel"].shape[-1] // 16)
        for j in range(3):
            x = silu(layer_norm(self.deconv(p[f"ConvTranspose_{j}"]["kernel"], x), **p[f"LayerNorm_{j}"]))
        x = self.deconv(p["head"]["kernel"], x) + p["head"]["bias"]
        x = jnp.moveaxis(x, -1, -3)
        return x.reshape(*lead, *x.shape[-3:])

    def decode_vec(self, wm, latent):
        p = wm["observation_model_mlp"]
        return self.dense(p["head_reward"], self.mlp(p["MLP_0"], latent, self.S["mlp_layers"]))

    def head(self, wm, name, latent):
        x = self.mlp(wm[f"{name}_model"]["layers_0"], latent, self.S["mlp_layers"])
        return self.dense(wm[f"{name}_head"], x)

    def unimix(self, logits):
        S = self.S
        shaped = logits.reshape(*logits.shape[:-1], -1, S["discrete_size"])
        probs = jax.nn.softmax(shaped, -1)
        probs = (1.0 - S["unimix"]) * probs + S["unimix"] / S["discrete_size"]
        return jnp.log(probs)  # [..., stoch, discrete]

    def recurrent(self, wm, x, h):
        p = wm["rssm"]["recurrent_model"]
        feat = self.mlp(p["input_proj"], x, 1)
        proj = self.dense(p["rnn"]["Dense_0"], jnp.concatenate([feat, h], -1))
        n = layer_norm(proj, p["rnn"]["ln_scale"], p["rnn"]["ln_bias"])
        H = h.shape[-1]
        reset = jax.nn.sigmoid(n[..., :H])
        cand = jnp.tanh(reset * n[..., H : 2 * H])
        update = jax.nn.sigmoid(n[..., 2 * H :] - 1.0)
        return update * cand + (1.0 - update) * h

    def prior_logits(self, wm, h):
        r = wm["rssm"]
        return self.unimix(self.dense(r["trans_logits"], self.mlp(r["transition_model"]["layers_0"], h, 1)))

    def post_logits(self, wm, h, embed):
        r = wm["rssm"]
        x = jnp.concatenate([h, embed], -1)
        return self.unimix(self.dense(r["repr_logits"], self.mlp(r["representation_model"]["layers_0"], x, 1)))

    def actor_logits(self, actor, latent):
        S = self.S
        x = self.mlp(actor["MLP_0"], latent, S["mlp_layers"])
        logits = self.dense(actor["head_0"], x)
        probs = (1.0 - S["unimix"]) * jax.nn.softmax(logits, -1) + S["unimix"] / logits.shape[-1]
        return jax.nn.log_softmax(jnp.log(probs), -1)

    def critic_logits(self, critic, latent):
        return self.dense(critic["head"], self.mlp(critic["MLP_0"], latent, self.S["mlp_layers"]))


def sample_onehot(key, logits):
    """Straight-through one-hot draw: hard sample forward, probabilities backward."""
    logp = jax.nn.log_softmax(logits, -1)
    idx = jax.random.categorical(key, logp, axis=-1, shape=logp.shape[:-1])
    hard = jax.nn.one_hot(idx, logp.shape[-1], dtype=logp.dtype)
    probs = jnp.exp(logp)
    return hard + probs - jax.lax.stop_gradient(probs)


def mode_onehot(logits):
    return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1], dtype=logits.dtype)


def twohot_log_prob(logits, x, low=-20.0, high=20.0):
    """log-prob [..., 1] of the raw-space scalar x [..., 1] under two-hot bins."""
    bins = logits.shape[-1]
    logp = jax.nn.log_softmax(logits, -1)
    y = jnp.clip(symlog(x), low, high)
    buckets = jnp.linspace(low, high, bins, dtype=jnp.float32)
    size = (high - low) / (bins - 1)
    right = jnp.clip(jnp.searchsorted(buckets, y, side="left"), 0, bins - 1)
    left = jnp.clip(right - 1, 0, bins - 1)
    left_w = jnp.abs(buckets[right] - y) / size
    target = jax.nn.one_hot(left[..., 0], bins) * left_w + jax.nn.one_hot(right[..., 0], bins) * (1.0 - left_w)
    return (target * logp).sum(-1, keepdims=True)


def twohot_mean(logits, low=-20.0, high=20.0):
    probs = jax.nn.softmax(logits, -1)
    support = jnp.linspace(low, high, logits.shape[-1], dtype=jnp.float32)
    return symexp((probs * support).sum(-1, keepdims=True))


def categorical_kl(post, prior):
    post_lp, prior_lp = jax.nn.log_softmax(post, -1), jax.nn.log_softmax(prior, -1)
    return (jnp.exp(post_lp) * (post_lp - prior_lp)).sum(-1).sum(-1)


def bernoulli_log_prob(logits, x):
    return -jnp.maximum(logits, 0) + logits * x - jnp.log1p(jnp.exp(-jnp.abs(logits)))


# --------------------------------------------------------------------------- optimizer
def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def clipped_adam(params, grads, state, lr, eps, clip):
    """Global-norm clipping, then Adam with bias correction.  Returns the new
    parameters, the new state and the gradient as Adam received it."""
    gn = global_norm(grads)
    scale = jnp.where(gn < clip, 1.0, clip / gn)
    grads = jax.tree.map(lambda g: g * scale, grads)
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * jnp.square(g), state["nu"], grads)
    c = count.astype(jnp.float32)
    mu_hat = jax.tree.map(lambda m: m / (1 - ADAM_B1**c), mu)
    nu_hat = jax.tree.map(lambda v: v / (1 - ADAM_B2**c), nu)
    new = jax.tree.map(lambda p, m, v: p - lr * m / (jnp.sqrt(v) + eps), params, mu_hat, nu_hat)
    return new, {"count": count, "mu": mu, "nu": nu}, grads


def init_state(params):
    return {
        "params": params,
        "opt": {k: adam_init(params[k]) for k in ("world_model", "actor", "critic")},
        "moments": {"low": jnp.zeros(()), "high": jnp.zeros(())},
    }


# --------------------------------------------------------------------------- the step
def train_step(S: Dict[str, Any], state, batch, key, quant: str = "f32"):
    """One gradient step.  ``batch``: rgb [T,B,C,H,W] uint8, reward [T,B,1] (the
    observation key), actions [T,B,A], rewards, terminated, is_first [T,B,1].
    Returns the new state and a dict of readings (losses and, per tree, the gradient
    as the optimizer received it)."""
    net = Net(S, quant)
    params = state["params"]
    T, B = batch["rewards"].shape[:2]
    stoch, disc = S["stochastic_size"], S["discrete_size"]
    rec_size = S["recurrent_state_size"]
    k_wm, k_img, k_a0 = jax.random.split(key, 3)
    is_first = batch["is_first"].at[0].set(1.0)
    actions_in = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)

    def wm_loss(wmp):
        wm = wmp["params"]
        embed = net.encode(wm, batch["rgb"], batch["reward"])
        h_init = jnp.broadcast_to(jnp.tanh(wm["rssm"]["initial_recurrent_state"]), (B, rec_size))
        z_init = mode_onehot(net.prior_logits(wm, h_init)).reshape(B, -1)

        def step(carry, x):
            post, h = carry
            action, emb, first, k = x
            action = (1 - first) * action
            h = (1 - first) * h + first * h_init
            post = (1 - first) * post + first * z_init
            h = net.recurrent(wm, jnp.concatenate([post, action], -1), h)
            _, k2 = jax.random.split(k)  # the first half keys the prior draw, which the loss never uses
            prior_l = net.prior_logits(wm, h)
            post_l = net.post_logits(wm, h, emb)
            post = sample_onehot(k2, post_l).reshape(B, -1)
            return (post, h), (h, post, post_l, prior_l)

        keys = jax.random.split(k_wm, T)
        init = (jnp.zeros((B, stoch * disc)), jnp.zeros((B, rec_size)))
        _, (recs, posts, post_l, prior_l) = jax.lax.scan(step, init, (actions_in, embed, is_first, keys))
        latents = jnp.concatenate([posts, recs], -1)
        target = batch["rgb"].astype(jnp.float32) / 255.0 - 0.5
        obs_lp = -jnp.square(net.decode_rgb(wm, latents) - target).sum((-3, -2, -1))
        obs_lp = obs_lp - jnp.square(net.decode_vec(wm, latents) - symlog(batch["reward"])).sum(-1)
        reward_lp = twohot_log_prob(net.head(wm, "reward", latents), batch["rewards"]).sum(-1)
        cont_lp = bernoulli_log_prob(net.head(wm, "continue", latents), 1.0 - batch["terminated"]).sum(-1)
        sg = jax.lax.stop_gradient
        kl = categorical_kl(sg(post_l), prior_l)
        dyn = S["kl_dynamic"] * jnp.maximum(kl, S["kl_free_nats"])
        rep = S["kl_representation"] * jnp.maximum(categorical_kl(post_l, sg(prior_l)), S["kl_free_nats"])
        loss = (S["kl_regularizer"] * (dyn + rep) - obs_lp - reward_lp - S["continue_scale_factor"] * cont_lp).mean()
        return loss, (posts, recs, kl)

    (loss_wm, (posts, recs, kl)), g_wm = jax.value_and_grad(wm_loss, has_aux=True)(params["world_model"])
    o = S["optimizers"]["world_model"]
    new_wm, opt_wm, g_wm = clipped_adam(params["world_model"], g_wm, state["opt"]["world_model"], o["lr"], o["eps"], o["clip"])
    wm = new_wm["params"]

    sg = jax.lax.stop_gradient
    latent0 = sg(jnp.concatenate([posts, recs], -1)).reshape(T * B, -1)
    prior0 = sg(posts).reshape(T * B, -1)
    rec0 = sg(recs).reshape(T * B, -1)
    cont0 = (1.0 - batch["terminated"]).reshape(T * B, 1)
    gamma, lmbda, H = S["gamma"], S["lmbda"], S["horizon"]
    critic = params["critic"]["params"]

    def actor_loss(ap):
        actor = ap["params"]
        # one head: the key is split once, as for a tuple of heads
        a0 = sample_onehot(jax.random.split(k_a0, 1)[0], net.actor_logits(actor, latent0))

        def img(carry, k):
            prior, h, action = carry
            k_dyn, k_act = jax.random.split(k)
            h = net.recurrent(wm, jnp.concatenate([prior, action], -1), h)
            prior = sample_onehot(k_dyn, net.prior_logits(wm, h)).reshape(T * B, -1)
            latent = jnp.concatenate([prior, h], -1)
            action = sample_onehot(jax.random.split(k_act, 1)[0], net.actor_logits(actor, sg(latent)))
            return (prior, h, action), (latent, action)

        _, (lat_img, act_img) = jax.lax.scan(img, (prior0, rec0, a0), jax.random.split(k_img, H))
        traj = jnp.concatenate([latent0[None], lat_img], 0)
        acts = jnp.concatenate([a0[None], act_img], 0)
        values = twohot_mean(net.critic_logits(critic, traj))
        rewards = twohot_mean(net.head(wm, "reward", traj))
        conts = (jax.nn.sigmoid(net.head(wm, "continue", traj)) > 0.5).astype(jnp.float32)
        conts = jnp.concatenate([cont0[None], conts[1:]], 0)
        interm = rewards[1:] + conts[1:] * gamma * values[1:] * (1 - lmbda)

        def lam(carry, x):
            it, ct = x
            carry = it + ct * gamma * lmbda * carry
            return carry, carry

        _, lambda_values = jax.lax.scan(lam, values[-1], (interm, conts[1:]), reverse=True)
        discount = sg(jnp.cumprod(conts * gamma, 0) / gamma)
        mo = S["moments"]
        lv = sg(lambda_values)
        low = mo["decay"] * state["moments"]["low"] + (1 - mo["decay"]) * jnp.quantile(lv, mo["low"])
        high = mo["decay"] * state["moments"]["high"] + (1 - mo["decay"]) * jnp.quantile(lv, mo["high"])
        invscale = jnp.maximum(1.0 / mo["max"], high - low)
        advantage = (lambda_values - low) / invscale - (values[:-1] - low) / invscale
        logp_all = net.actor_logits(actor, sg(traj))
        logpi = (logp_all * sg(acts)).sum(-1)[:-1]
        entropy = -(jnp.exp(logp_all) * logp_all).sum(-1)
        objective = logpi[..., None] * sg(advantage)
        loss = -jnp.mean(discount[:-1] * (objective + S["ent_coef"] * entropy[:-1][..., None]))
        return loss, (sg(traj), lv, discount, {"low": low, "high": high})

    (loss_actor, (traj, lambda_values, discount, moments)), g_actor = jax.value_and_grad(actor_loss, has_aux=True)(
        params["actor"]
    )
    o = S["optimizers"]["actor"]
    new_actor, opt_actor, g_actor = clipped_adam(params["actor"], g_actor, state["opt"]["actor"], o["lr"], o["eps"], o["clip"])

    target = params["target_critic"]["params"]

    def critic_loss(cp):
        logits = net.critic_logits(cp["params"], traj[:-1])
        tv = sg(twohot_mean(net.critic_logits(target, traj[:-1])))
        lp = twohot_log_prob(logits, lambda_values).sum(-1) + twohot_log_prob(logits, tv).sum(-1)
        return jnp.mean(-lp * discount[:-1][..., 0])

    loss_critic, g_critic = jax.value_and_grad(critic_loss)(params["critic"])
    o = S["optimizers"]["critic"]
    new_critic, opt_critic, g_critic = clipped_adam(
        params["critic"], g_critic, state["opt"]["critic"], o["lr"], o["eps"], o["clip"]
    )
    tau = S["tau"]
    new_target = jax.tree.map(lambda tp, cp: (1 - tau) * tp + tau * cp, params["target_critic"], new_critic)
    new_state = {
        "params": {"world_model": new_wm, "actor": new_actor, "critic": new_critic, "target_critic": new_target},
        "opt": {"world_model": opt_wm, "actor": opt_actor, "critic": opt_critic},
        "moments": moments,
    }
    readings = {
        "loss": {"world_model": loss_wm, "actor": loss_actor, "critic": loss_critic},
        "grads": {"world_model": g_wm, "actor": g_actor, "critic": g_critic},
        # the KL of every state of the batch, to be held against the free nats
        "kl": {"mean": kl.mean(), "min": kl.min()},
    }
    return new_state, readings


def step_key(base_key, start_count, index: int = 0, block: int = 1):
    """The key of gradient step ``start_count + index`` of a block of ``block`` steps
    dispatched with ``base_key``: the schedule the published block scan uses."""
    return jax.random.split(jax.random.fold_in(base_key, start_count), block)[index]
