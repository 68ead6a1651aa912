"""A ``lax.scan`` over a flax step whose dense kernels get their gradient
after the backward loop, not inside it.

Reverse mode through ``lax.scan(lambda c, x: step(params, c, x), ...)``
carries one float32 accumulator of every closed-over parameter through the
backward loop and adds one timestep's share at each step. For a vector that
costs nothing. For the kernel ``W`` of a dense layer it is a read and a write
of the whole ``[in, out]`` array per step, to add ``x_t^T dy_t``: a product
whose inner dimension is the batch. :func:`hoisted_scan` keeps in the loop
what is sequential (the carry's gradient, the inputs', the vectors') and takes
every such kernel out:

- the step runs with the kernel held constant and a zero *probe* added to the
  layer's output ``y_t = x_t W + b``. The probes are scanned over, so the
  backward loop emits their gradient ``dy_t`` stacked per step (a scan stacks
  the cotangents of its ``xs``, it does not accumulate them);
- the forward loop stacks the layer's input ``x_t`` beside its outputs;
- after the loop ``dW = sum_t x_t^T dy_t`` is one contraction over ``T x B``
  with float32 accumulation, on operands of the dtype the per-step product had.

Which kernels: every ``nn.Dense`` the step calls whose kernel is a leaf of
``params`` (found by tracing the step once on shapes). A kernel the step reads
any other way, such as the Pallas GRU step that takes its weights as arrays,
keeps its per-step accumulation. A variable of a flax module is read by that
module alone, so no kernel is in both sets.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any
Step = Callable[[Pytree, Pytree, Pytree, Pytree], Tuple[Pytree, Pytree]]


class DenseCall(NamedTuple):
    """One call of an ``nn.Dense`` in the step, in trace order."""

    leaf: int  # the kernel's index among ``jax.tree.leaves(params)``
    out: jax.ShapeDtypeStruct  # the layer's output: the probe's shape, the product's dtype
    precision: Any


class HoistPlan(NamedTuple):
    calls: Tuple[DenseCall, ...]
    in_loop: Tuple[int, ...]  # leaves: 2-D parameters of the step's other modules

    def counters(self, params: Pytree) -> Dict[str, int]:
        """The ``dv3/rssm_scan``-style counters event's fields (howto/telemetry.md)."""
        leaves = jax.tree.leaves(params)
        hoisted = {c.leaf for c in self.calls}
        return {
            "hoisted_kernels": len(hoisted),
            "hoisted_bytes": int(sum(4 * np.prod(leaves[i].shape) for i in hoisted)),
            "in_loop_kernels": len(self.in_loop),
        }


class _Tap:
    """The interceptor of one trace of the step on ``params``. Without probes
    it only notes what the step calls; with them it adds the next probe to each
    planned dense layer's output and keeps the layer's input."""

    def __init__(self, params: Pytree, probes: Optional[List[jax.Array]] = None):
        paths, _ = jax.tree_util.tree_flatten_with_path(params)
        self.index = {tuple(getattr(k, "key", k) for k in path): i for i, (path, _) in enumerate(paths)}
        self.shapes = [tuple(leaf.shape) for _, leaf in paths]
        self.probes = probes
        self.calls: List[DenseCall] = []
        self.inputs: List[jax.Array] = []
        self.modules: set = set()

    def kernel_of(self, mod: nn.Module) -> Optional[int]:
        leaf = self.index.get(("params", *mod.path, "kernel"))
        plain = isinstance(mod, nn.Dense) and mod.dot_general is None and mod.dot_general_cls is None
        if leaf is None or not plain or self.shapes[leaf] != mod.variables["params"]["kernel"].shape:
            return None
        return leaf

    def __call__(self, next_fun, args, kwargs, context):
        y = next_fun(*args, **kwargs)
        mod = context.module
        self.modules.add(mod.path)
        leaf = self.kernel_of(mod) if context.method_name == "__call__" else None
        if leaf is None:
            return y
        self.calls.append(DenseCall(leaf, jax.ShapeDtypeStruct(y.shape, y.dtype), mod.precision))
        if self.probes is not None:
            # the operand of the layer's product: its input in the dtype the layer computes in
            self.inputs.append(args[0].astype(y.dtype))
            y = y + self.probes[len(self.inputs) - 1]
        return y


def _one_step(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree)


def hoist_plan(step: Step, params: Pytree, init: Pytree, xs: Pytree, frozen_xs: Pytree) -> HoistPlan:
    """Trace one ``step(params, carry, x, frozen_x)`` on shapes and say which
    kernels :func:`hoisted_scan` takes out of the backward loop."""
    tap = _Tap(params)

    def traced(*args):
        with nn.intercept_methods(tap):
            return step(*args)

    jax.eval_shape(traced, params, init, _one_step(xs), _one_step(frozen_xs))
    hoisted = {c.leaf for c in tap.calls}
    in_loop = tuple(
        i for path, i in tap.index.items()
        if len(tap.shapes[i]) == 2 and i not in hoisted and path[0] == "params" and path[1:-1] in tap.modules
    )  # fmt: skip
    return HoistPlan(tuple(tap.calls), in_loop)


def _zero_cotangent(tree: Pytree) -> Pytree:
    def zero(a):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.zeros_like(a)
        return np.zeros(a.shape, jax.dtypes.float0)

    return jax.tree.map(zero, tree)


def _merge(treedef: Any, picked: Dict[int, Any], rest: List[Any]) -> Pytree:
    """The tree with ``picked`` at their leaves' indices and ``rest``, in order, at the others."""
    it = iter(rest)
    return treedef.unflatten([picked[i] if i in picked else next(it) for i in range(treedef.num_leaves)])


def hoisted_scan(step: Step, params: Pytree, init: Pytree, xs: Pytree, frozen_xs: Pytree) -> Tuple[Pytree, Pytree]:
    """``lax.scan(lambda c, x: step(params, c, *x), init, (xs, frozen_xs))``:
    the same values, and the same gradients with respect to ``params`` and
    ``xs``. ``init`` and ``frozen_xs`` (keys, masks, data) get none."""
    plan = hoist_plan(step, params, init, xs, frozen_xs)
    leaves, treedef = jax.tree.flatten(params)
    hoisted = {c.leaf for c in plan.calls}
    length = jax.tree.leaves((xs, frozen_xs))[0].shape[0]

    def plain(params, xs, init, frozen_xs):
        return jax.lax.scan(lambda c, x: step(params, c, *x), init, (xs, frozen_xs))

    if not hoisted:
        return plain(params, xs, init, frozen_xs)

    def fwd(params, xs, init, frozen_xs):
        flat = jax.tree.leaves(params)
        kernels = {i: flat[i] for i in hoisted}
        rest = [leaf for i, leaf in enumerate(flat) if i not in hoisted]
        probes = [jnp.zeros((length, *c.out.shape), c.out.dtype) for c in plan.calls]

        def probed(rest, xs, probes):
            p = _merge(treedef, kernels, rest)

            def body(carry, x):
                x, frozen_x, probes_t = x
                tap = _Tap(p, probes_t)
                with nn.intercept_methods(tap):
                    carry, y = step(p, carry, x, frozen_x)
                return carry, (y, tap.inputs)

            carry, (ys, inputs) = jax.lax.scan(body, init, (xs, frozen_xs, probes))
            return (carry, ys), inputs

        out, vjp, inputs = jax.vjp(probed, rest, xs, probes, has_aux=True)
        return out, (vjp, inputs, init, frozen_xs)

    def bwd(residuals, ct):
        vjp, inputs, init, frozen_xs = residuals
        d_rest, d_xs, d_probes = vjp(ct)
        d_kernels: Dict[int, jax.Array] = {}
        for call, x, dy in zip(plan.calls, inputs, d_probes):
            dw = jnp.einsum(
                "ni,no->io",
                x.reshape(-1, x.shape[-1]),
                dy.reshape(-1, dy.shape[-1]),
                precision=call.precision,
                preferred_element_type=jnp.float32,
            ).astype(leaves[call.leaf].dtype)
            d_kernels[call.leaf] = d_kernels[call.leaf] + dw if call.leaf in d_kernels else dw
        return _merge(treedef, d_kernels, d_rest), d_xs, _zero_cotangent(init), _zero_cotangent(frozen_xs)

    run = jax.custom_vjp(plain)
    run.defvjp(fwd, bwd)
    return run(params, xs, init, frozen_xs)
