"""Operations of one DreamerV3 gradient step, from the configuration's shapes.

Counted: every matrix multiplication and convolution of the forward pass (2 x rows x
in x out), twice that again for the backward pass where a gradient is taken (weight
and input gradients; a first layer whose input needs no gradient counts once), and the
optimizer's elementwise work.  Not counted: normalisations, activations, softmaxes,
sampling (under 1% of the total at these widths) and anything recomputed.

Where gradients flow is the algorithm's, not the implementation's: the world-model
loss trains the world model (forward + backward); the imagination rollout, the
critic's values and the reward/continue heads on the imagined trajectory are forward
only (for discrete actions the policy gradient is REINFORCE on a stop-gradient
advantage); the actor's log-probabilities over the trajectory and the critic's loss
are forward + backward; the target critic is forward only.
"""

from __future__ import annotations

from typing import Any, Dict

ADAM_FLOPS_PER_PARAM = 18.0  # clip (3) + moments (7) + bias correction and update (8)
EMA_FLOPS_PER_PARAM = 3.0


def mm(rows: float, d_in: float, d_out: float) -> float:
    return 2.0 * rows * d_in * d_out


def mlp(rows: float, d_in: float, units: float, layers: int) -> float:
    return mm(rows, d_in, units) + (layers - 1) * mm(rows, units, units)


def count_params(S: Dict[str, Any]) -> Dict[str, float]:
    from perfbench.reference.dreamer_v3 import flat_shapes

    out = {"world_model": 0.0, "actor": 0.0, "critic": 0.0, "target_critic": 0.0}
    for path, shape in flat_shapes(S).items():
        n = 1.0
        for d in shape:
            n *= d
        out[path.split("/")[0]] += n
    return out


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    """``{"total": ..., <component>: ...}`` floating-point operations of one step."""
    T, B, H = S["sequence_length"], S["batch_size"], S["horizon"]
    N = float(T * B)
    m, units, layers = S["cnn_channels_multiplier"], S["dense_units"], S["mlp_layers"]
    stoch = S["stochastic_size"] * S["discrete_size"]
    rec, A, C, vec = S["recurrent_state_size"], S["actions"], S["image_channels"], S["vector_obs_dim"]
    lat = stoch + rec
    ht, hr = S["transition_hidden_size"], S["representation_hidden_size"]
    enc_c = [m * 2**i for i in range(4)]
    embed = enc_c[-1] * 16 + units

    conv, cin, side = [], C, S["image_size"]
    for c in enc_c:
        side //= 2
        conv.append(2.0 * N * side * side * 16 * cin * c)
        cin = c
    deconv, side = [], 4
    for c in list(reversed(enc_c[:-1])) + [C]:
        deconv.append(2.0 * N * side * side * 16 * cin * c)
        cin, side = c, side * 2

    gru = lambda rows: mm(rows, stoch + A, units) + mm(rows, units + rec, 3 * rec)  # noqa: E731
    prior = lambda rows: mm(rows, rec, ht) + mm(rows, ht, stoch)  # noqa: E731
    post = lambda rows: mm(rows, rec + embed, hr) + mm(rows, hr, stoch)  # noqa: E731
    head = lambda rows, out: mlp(rows, lat, units, layers) + mm(rows, units, out)  # noqa: E731

    wm_fwd = {
        "encoder": sum(conv) + mlp(N, vec, units, layers),
        "rssm_scan": gru(N) + prior(N) + post(N),
        "decoder": mm(N, lat, 16 * enc_c[-1]) + sum(deconv) + head(N, vec),
        "reward_continue_heads": head(N, S["reward_bins"]) + head(N, 1),
    }
    # backward = 2 x forward, less the input gradient of the very first convolution and
    # of the vector encoder's first layer (their inputs are data)
    wm_total = 3.0 * sum(wm_fwd.values()) - conv[0] - mm(N, vec, units)
    imagination = mlp(N, lat, units, layers) + mm(N, units, A)  # a0
    imagination += H * (gru(N) + prior(N) + head(N, A))
    traj, traj1 = (H + 1) * N, H * N
    behaviour_fwd = head(traj, S["critic_bins"]) + head(traj, S["reward_bins"]) + head(traj, 1)
    actor_train = 3.0 * head(traj, A) - mm(traj, lat, units)
    critic_train = 3.0 * head(traj1, S["critic_bins"]) - mm(traj1, lat, units) + head(traj1, S["critic_bins"])
    n = count_params(S)
    optimizer = ADAM_FLOPS_PER_PARAM * (n["world_model"] + n["actor"] + n["critic"]) + EMA_FLOPS_PER_PARAM * n["critic"]
    out = {
        "world_model": wm_total,
        "world_model.encoder_fwd": wm_fwd["encoder"],
        "world_model.rssm_scan_fwd": wm_fwd["rssm_scan"],
        "world_model.decoder_fwd": wm_fwd["decoder"],
        "world_model.heads_fwd": wm_fwd["reward_continue_heads"],
        "imagination": imagination,
        "behaviour_heads_fwd": behaviour_fwd,
        "actor_train": actor_train,
        "critic_train": critic_train,
        "optimizer": optimizer,
    }
    out["total"] = wm_total + imagination + behaviour_fwd + actor_train + critic_train + optimizer
    return out
