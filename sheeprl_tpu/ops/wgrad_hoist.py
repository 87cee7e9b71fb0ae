"""A `lax.scan` whose backward pass carries no array of a kernel's shape.

Autodiff of a scan that applies a Dense to B rows at each of T steps keeps
one float32 accumulator per kernel in the backward loop and does
``acc += x_t.T @ dy_t`` T times: every step reads and writes the whole
kernel for a B-row matmul, so the weight gradient is bound by the HBM
traffic of its own accumulator (at DreamerV3-XL the GRU's fused kernel is
252 MB and its gradient was 48 ms of a 176 ms train step; PERF.md, PR 35).
The sum over steps does not have to happen inside the loop: with the
Dense's inputs ``x`` [T, B, K] kept from the forward and the cotangents of
its outputs ``dy`` [T, B, N] stacked by the backward scan,
``dW = einsum("tbk,tbn->kn", x, dy)`` is ONE matmul of T*B rows after it.

`scan` does that for every `HoistableDense` the step function applies, by
whatever path the step reaches it (a flax ``apply`` of any method of any
module), through two flax collections and no change to a module's
signature:

* `TAPE` (mutable): each call of a `HoistableDense` appends its input and,
  where no perturbation is handed in, its output (the probe that finds the
  calls and their shapes);
* `PERTURB` (read): per call one array of the output's shape that the Dense
  adds to its output. It is zero; its cotangent is the output's.

With neither collection in an ``apply`` a `HoistableDense` is ``nn.Dense``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

TAPE = "wgrad_tape"
PERTURB = "wgrad_perturbations"


class HoistableDense(nn.Dense):
    """``nn.Dense`` whose weight gradient `scan` can compute after the
    backward loop. The parameters and, with neither `TAPE` mutable nor
    `PERTURB` given, the outputs are ``nn.Dense``'s; inside an
    ``nn.compact`` body give it the name ``nn.Dense`` would get
    (``Dense_0``), the automatic one follows the class."""

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        y = super().__call__(inputs)
        if self.is_initializing() or not self.is_mutable_collection(TAPE):
            return y
        # one module may be applied several times in a step (the RSSM's
        # transition head: to the initial state and to the new one)
        call = len(self.get_variable(TAPE, "inputs")) if self.has_variable(TAPE, "inputs") else 0
        self.sow(TAPE, "inputs", inputs)
        if self.has_variable(PERTURB, "outputs"):
            return y + self.get_variable(PERTURB, "outputs")[call]
        self.sow(TAPE, "outputs", y)
        return y


Step = Callable[[Any, Optional[Dict[str, Any]], Any, Any, Any], Tuple[Any, Any, Dict[str, Any]]]


def _zero_cotangent(x: jax.Array):
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros(x.shape, x.dtype)
    return np.zeros(x.shape, jax.dtypes.float0)  # integers, PRNG keys


def _contract(x: jax.Array, dy: jax.Array, dtype: Any) -> jax.Array:
    """``x.T @ dy`` over every leading axis, on operands of the dtype the
    Dense multiplied in, accumulated and returned in ``dtype`` (the kernel's)."""
    x = x.reshape(-1, x.shape[-1]).astype(dy.dtype)
    dy = dy.reshape(-1, dy.shape[-1])
    return jax.lax.dot_general(x, dy, (((0,), (0,)), ((), ())), preferred_element_type=dtype)


def scan(
    step: Step,
    params: Any,
    carry0: Any,
    xs: Any,
    held_xs: Any = (),
    report: Optional[Callable[[Dict[str, Any]], None]] = None,
):
    """``lax.scan`` over the leading axis of ``xs`` and ``held_xs`` of

        ``step(params, perturbations, carry, x, held_x) -> (carry, y, tape)``

    where the step hands ``perturbations`` (None, or the `PERTURB`
    collection of this step) to its flax ``apply`` with `TAPE` mutable and
    returns the `TAPE` collection that ``apply`` gave back. Returns
    ``(carry, ys)`` as ``lax.scan`` does. Differentiable (once, in reverse)
    with respect to ``params``, ``carry0`` and ``xs``; ``held_xs`` are
    scanned inputs nothing is differentiated by (actions, flags, keys).

    The gradient of the kernel of every `HoistableDense` the step applies is
    contracted after the backward scan; every other parameter's comes out of
    the scan's own transpose as before. ``report``, where given, is called
    while tracing with what was hoisted: ``kernels``, their ``kernel_bytes``
    and the ``rows`` of each contraction."""
    x0, held0 = jax.tree.map(lambda a: a[0], (xs, held_xs))
    probe = jax.eval_shape(lambda p, c, x, h: step(p, None, c, x, h)[2], params, carry0, x0, held0)
    calls = {path[:-1]: outs for path, outs in flatten_dict(probe).items() if path[-1] == "outputs"}
    T = jax.tree.leaves((xs, held_xs))[0].shape[0]
    rest = flatten_dict(params)
    kernels = {path + ("kernel",): rest.pop(path + ("kernel",)) for path in calls}
    if report is not None:
        report(
            {
                "kernels": len(kernels),
                "kernel_bytes": sum(k.size * 4 for k in kernels.values()),
                "rows": max((T * int(np.prod(outs[0].shape[:-1])) for outs in calls.values()), default=0),
            }
        )

    def run(rest, kernels, carry0, xs, held_xs, perturbations):
        whole = unflatten_dict({**rest, **kernels})

        def body(carry, scanned):
            x, held, perturbation = scanned
            carry, y, tape = step(whole, unflatten_dict(perturbation), carry, x, held)
            tape = flatten_dict(tape)
            return carry, (y, {path: tape[path + ("inputs",)] for path in calls})

        carry, (ys, inputs) = jax.lax.scan(body, carry0, (xs, held_xs, perturbations))
        return (carry, ys), inputs

    def zeros():
        return {path + ("outputs",): tuple(jnp.zeros((T,) + o.shape, o.dtype) for o in outs) for path, outs in calls.items()}

    @jax.custom_vjp
    def hoisted(rest, kernels, carry0, xs, held_xs):
        return run(rest, kernels, carry0, xs, held_xs, zeros())[0]

    def forward(rest, kernels, carry0, xs, held_xs):
        out, vjp, inputs = jax.vjp(
            lambda rest, carry0, xs, perturbations: run(rest, kernels, carry0, xs, held_xs, perturbations),
            rest, carry0, xs, zeros(), has_aux=True,
        )
        return out, (vjp, inputs)

    def backward(residuals, cotangents):
        vjp, inputs = residuals
        d_rest, d_carry0, d_xs, d_outputs = vjp(cotangents)
        d_kernels = {}
        for path, kernel in kernels.items():  # of the enclosing call: read for its dtype alone
            terms = [
                _contract(x, dy, kernel.dtype)
                for x, dy in zip(inputs[path[:-1]], d_outputs[path[:-1] + ("outputs",)])
            ]
            d_kernels[path] = sum(terms[1:], terms[0])
        return d_rest, d_kernels, d_carry0, d_xs, jax.tree.map(_zero_cotangent, held_xs)  # by shape and dtype

    hoisted.defvjp(forward, backward)
    return hoisted(rest, kernels, carry0, xs, held_xs)
