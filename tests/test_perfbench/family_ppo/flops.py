"""Matmul flops of one update from the configuration's shapes (forward + backward of
the three MLPs over the batch, each epoch)."""

from typing import Any, Dict


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    D, U, F, L, A = S["obs_dim"], S["dense_units"], S["features_dim"], S["mlp_layers"], S["actions"]
    trunk = lambda n_in: n_in * U + (L - 1) * U * U  # noqa: E731
    weights = trunk(D) + U * F + trunk(F) + U * A + trunk(F) + U
    return {"total": 6.0 * weights * S["batch"] * S["update_epochs"]}
