"""A scan whose dense kernels get their gradient after the backward loop, not inside it.

JAX transposes ``lax.scan`` by carrying the cotangent of every closed-over constant
through the backward loop: each step forms ``dW_t = in_t^T @ dpre_t`` (a matmul with
K = batch rows whose *output* is as large as the weights) and adds it to a float32
accumulator of the weights' size in the loop's carry.  For a recurrent cell applied to
a few rows that read-modify-write of the accumulators is most of the backward loop's
memory traffic (DreamerV3-XL's RSSM: 352 MB read and written a step beside the 176 MB
of weights, PERF.md PR 26).  ``dW = sum_t in_t^T @ dpre_t`` is one matmul over the
stacked ``[T * rows, in]`` and ``[T * rows, out]``.

:func:`scan` differentiates the loop by JAX's own scan transposition with respect to
everything except the kernels (carry, scanned inputs, the other parameters, and a
zero perturbation on each kernel application's output), takes each application's
input (forward) and output cotangent (backward) out of the loops as stacked arrays,
and forms every kernel's gradient afterwards with one ``dot_general`` that
accumulates in float32.  Nothing of the forward pass is recomputed.
:func:`dense_scan` is the same for a step that applies Flax modules: every
``nn.Dense`` the step calls is such a kernel.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


@jax.custom_vjp
def _perturb(out: jax.Array, eps: jax.Array) -> jax.Array:
    """``out``, with ``eps`` receiving ``out``'s cotangent: what ``out + eps`` with
    ``eps = 0`` gives, without the add in the forward pass."""
    return out


_perturb.defvjp(lambda out, eps: (out, None), lambda _, g: (g, g))


class _Taps:
    """One step's kernel applications: ``tap(name, inp, out) -> out`` as the body calls
    it, keeping every application's operand and output as rows of two dimensions."""

    def __init__(self, eps: Optional[Dict[str, jax.Array]] = None) -> None:
        self.eps = eps
        self.ins: Dict[str, List[jax.Array]] = {}
        self.outs: Dict[str, List[jax.Array]] = {}

    def __call__(self, name: str, inp: jax.Array, out: jax.Array) -> jax.Array:
        rows = out.reshape(-1, out.shape[-1])
        start = sum(len(earlier) for earlier in self.outs.get(name, ()))
        self.ins.setdefault(name, []).append(inp.reshape(-1, inp.shape[-1]))
        self.outs.setdefault(name, []).append(rows)
        if self.eps is None:
            return out
        return _perturb(out, self.eps[name][start : start + len(rows)].reshape(out.shape))


def _stacked(parts: Dict[str, List[jax.Array]]) -> Dict[str, jax.Array]:
    return {name: jnp.concatenate(rows, 0) for name, rows in parts.items()}


def scan(
    body: Callable[..., Tuple[Any, Any]],
    params: Dict[str, jax.Array],
    init: Any,
    xs: Any,
    *,
    unroll: int = 1,
) -> Tuple[Any, Any, Dict[str, Tuple[int, ...]]]:
    """``lax.scan`` of ``body(params, carry, x, tap) -> (carry, y)`` over ``xs``.

    ``params`` is a flat ``name -> array`` dict.  The body passes every application of
    a dense kernel ``params[name]`` (``[in, out]``) through ``out = tap(name, inp,
    out)``, with ``inp`` the ``[..., in]`` operand as it enters the matmul (after any
    cast) and ``out`` anything ``[..., out]`` that the product reaches through
    additions alone (so after the bias is fine).  A kernel may be tapped several times
    a step.  The gradient of a tapped kernel is formed after the backward loop; every
    other gradient is JAX's own.

    Returns ``(carry, ys, deferred)``, ``deferred`` the shapes of the tapped kernels
    by name (static: decided while tracing)."""

    def tapped_outputs(params, init, xs):
        taps = _Taps()
        body(params, init, jax.tree.map(lambda x: x[0], xs), taps)
        return _stacked(taps.outs)

    # one step traced abstractly: which kernels it applies, and the rows that come out of each
    outs = jax.eval_shape(tapped_outputs, params, init, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    kernel_avals = {name: jax.ShapeDtypeStruct(params[name].shape, params[name].dtype) for name in outs}

    def run(kernels, rest, init, xs, eps):
        def step(carry, x_eps):
            x, eps_t = x_eps
            taps = _Taps(eps_t)
            carry, y = body({**rest, **kernels}, carry, x, taps)
            return carry, (y, _stacked(taps.ins))

        carry, (ys, ins) = jax.lax.scan(step, init, (xs, eps), unroll=unroll)
        return (carry, ys), ins

    @jax.custom_vjp
    def loop(kernels, rest, init, xs):
        return run(kernels, rest, init, xs, None)[0]

    def forward(kernels, rest, init, xs):
        eps = {name: jnp.zeros((length, *aval.shape), aval.dtype) for name, aval in outs.items()}
        out, pullback, ins = jax.vjp(lambda *rest_init_xs_eps: run(kernels, *rest_init_xs_eps), rest, init, xs, eps, has_aux=True)
        return out, (pullback, ins)

    def backward(residuals, cotangent):
        pullback, ins = residuals
        d_rest, d_init, d_xs, d_eps = pullback(cotangent)
        d_kernels = {
            name: jax.lax.dot_general(
                ins[name].reshape(-1, aval.shape[0]),
                d_eps[name].reshape(-1, aval.shape[1]),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(aval.dtype)
            for name, aval in kernel_avals.items()
        }
        return d_kernels, d_rest, d_init, d_xs

    loop.defvjp(forward, backward)
    kernels = {name: params[name] for name in outs}
    rest = {name: value for name, value in params.items() if name not in outs}
    carry, ys = loop(kernels, rest, init, xs)
    return carry, ys, {name: aval.shape for name, aval in kernel_avals.items()}


def dense_scan(
    step: Callable[[Any, Any, Any], Tuple[Any, Any]],
    variables: Any,
    init: Any,
    xs: Any,
    *,
    unroll: int = 1,
) -> Tuple[Any, Any, Dict[str, Tuple[int, ...]]]:
    """:func:`scan` for ``step(variables, carry, x) -> (carry, y)`` that applies Flax
    modules with ``variables`` (``module.apply(variables, ...)``): the kernel of every
    ``nn.Dense`` the step calls has its gradient deferred.  The variables' tree and
    names are what they were; ``deferred`` names a kernel by its path in them."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
    names = ["/".join(str(getattr(key, "key", key)) for key in path) for path, _ in leaves]

    def body(params, carry, x, tap):
        def dense(next_fun, args, kwargs, context):
            out = next_fun(*args, **kwargs)
            if type(context.module) is nn.Dense and context.method_name == "__call__":
                # the operand as Dense's own promotion hands it to the matmul
                out = tap("/".join(("params", *context.module.path, "kernel")), args[0].astype(out.dtype), out)
            return out

        with nn.intercept_methods(dense):
            return step(jax.tree_util.tree_unflatten(treedef, [params[name] for name in names]), carry, x)

    return scan(body, dict(zip(names, (leaf for _, leaf in leaves))), init, xs, unroll=unroll)
