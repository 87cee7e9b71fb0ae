"""`jit(train)` by part: the eight `jax.named_scope` names of `make_train_fn`'s
`one_step` reach the compiled HLO's `op_name`s, forward and backward, for the
coupled and the decoupled RSSM, so that a capture's reader can book each op to
its part (`perfbench/span_reduce.py`, whose component rule is the one used here)."""
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from dreamer_tiny import N_ACT, make_trainer  # noqa: E402
from perfbench.overrides import REHEARSAL_OVERRIDES  # noqa: E402
from perfbench.span_reduce import PARTS, part_of  # noqa: E402

RSSM = {"coupled": [], "decoupled": ["algo.world_model.decoupled_rssm=True"]}


@functools.lru_cache(maxsize=None)
def compiled_op_names(rssm):
    from sheeprl_tpu.telemetry import xla

    train, params, opt_states, moments = make_trainer(REHEARSAL_OVERRIDES + RSSM[rssm])
    T, B = 8, 4
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct((1, T, B) + shape, dtype)  # noqa: E731
    batch = {"rgb": sds(64, 64, 3, dtype=jnp.uint8), "actions": sds(N_ACT), "rewards": sds(1),
             "terminated": sds(1), "truncated": sds(1), "is_first": sds(1)}
    with xla.suppress_retrace_accounting():
        text = train.lower(params, opt_states, moments, batch, jax.random.split(jax.random.key(0), 1)).compile().as_text()
    return tuple(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_scope_is_a_component_of_some_compiled_instructions_op_name(rssm, part):
    names = [n for n in compiled_op_names(rssm) if part_of(n) == part]
    assert names, f"no instruction of the compiled train step lies under {part!r}"
    assert all(part in re.split(r"[/()]", n) for n in names)
    if part != "optimizer":  # every part but the optimizer is differentiated, and autodiff keeps the name
        assert any("jvp(" in n for n in names), names[:3]


@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_the_scan_of_a_part_stays_under_it_and_imagination_is_innermost_inside_actor(rssm):
    names = compiled_op_names(rssm)
    assert any(part_of(n) == "wm_rssm" and "/while/body/" in n.split("wm_rssm", 1)[1] for n in names)
    imagined = [n for n in names if part_of(n) == "imagination"]
    assert imagined and all(re.search(r"actor\)*/imagination/", n) for n in imagined)


@pytest.mark.parametrize("rssm", sorted(RSSM))
def test_the_kernels_gradients_are_contracted_after_the_backward_scan_and_under_wm_rssm(rssm):
    """`ops.wgrad_hoist.scan`'s matmuls over all T*B rows: outside the backward
    loop and outside every flax module, so their `op_name` is the transposed
    scope and the matmul, and the reader books them to the part."""
    after_the_scan = [n for n in compiled_op_names(rssm) if re.search(r"/transpose\(jvp\(wm_rssm\)\)/dot_general$", n)]
    assert after_the_scan, "no contraction follows the world model's backward scan"
    assert all(part_of(n) == "wm_rssm" and "/while/body/" not in n.split("wm_rssm", 1)[1] for n in after_the_scan)


def test_part_of_takes_whole_components_and_the_innermost_one():
    assert part_of("jit(train)/while/body/closed_call/transpose(jvp(wm_rssm))/while/body/mul") == "wm_rssm"
    assert part_of("jit(train)/while/body/jvp(actor)/imagination/while/body/WorldModel.imagination/dot_general") == "imagination"
    assert part_of("jit(train)/while/body/optimizer/sub") == "optimizer"
    assert part_of("jit(train)/while/body/Actor_0/actor_head/critic_like/add") is None  # no whole component
    assert part_of("jit(_gather_batch)/gather") is None and part_of("") is None
