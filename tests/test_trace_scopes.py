"""The step program's named phases are metadata only: the compiled relaxed
step is the same instruction for instruction with or without them, and
each phase that computes something names some instruction's op_name."""
import contextlib
import re

import jax
import pytest

from repro.configs import get_arch
from repro.configs.base import TrainConfig
from repro.data.synthetic import make_batches
from repro.training import train_loop

# ckpt_feed passes the batch's ids through and computes nothing
PHASES = ("bottom_mlp", "interaction", "top_mlp", "embed_grad",
          "dense_update", "embed_update", "prefetch")
_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
# the source locations of the instructions' metadata
_DEBUG_SECTIONS = {"FileNames", "FunctionNames", "FileLocations",
                   "StackFrames"}


def _instructions(hlo: str) -> list[str]:
    """The program's text less its metadata: each instruction's
    ``metadata={...}`` and the sections of source locations it points to."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in _DEBUG_SECTIONS:
            skip = True
        elif not line.strip():
            skip = False
        if not skip:
            out.append(_METADATA.sub("", line))
    return out


def _compiled_relaxed_step() -> str:
    cfg = get_arch("dlrm-rm1", smoke=True).model
    tc = TrainConfig(embed_learning_rate=0.05)
    init_fn, _, relaxed_step, warmup = train_loop.make_step_fns(cfg, tc)
    data = make_batches(cfg, 8, 16, seed=0)
    state = warmup(init_fn(jax.random.PRNGKey(0)), data.next(0))
    return jax.jit(relaxed_step).lower(state, data.next(0),
                                       data.next(1)).compile().as_text()


@pytest.fixture(scope="module")
def scoped():
    return _compiled_relaxed_step()


@pytest.fixture(scope="module")
def unscoped():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        return _compiled_relaxed_step()


def test_scopes_leave_the_compiled_program_unchanged(scoped, unscoped):
    assert scoped != unscoped                    # the metadata differs
    assert _instructions(scoped) == _instructions(unscoped)


@pytest.mark.parametrize("phase", PHASES)
def test_each_phase_names_some_instruction(scoped, unscoped, phase):
    def named(hlo):
        return any(re.search(rf"(^|[/(]){phase}([)/]|$)", n)
                   for n in re.findall(r'op_name="([^"]*)"', hlo))
    assert named(scoped) and not named(unscoped)
