"""The chip benchmark's correctness check on the CPU at tiny sizes: every
fault a cell can have, planted in the program underneath a run that
skips only the look for a chip, comes out as not correct; the sound run
comes out correct."""
import pytest

import tiny_cells

SERVE = ["--workload", "serve.tiny", "--seed", "9", "--seconds", "2"]

SERVE_FAULTS = """
import jax.numpy as jnp
from repro.serve.engine import ServeEngine
def token_altered():
    orig = ServeEngine._sample
    def sample(self, logits, key):
        tok = orig(self, logits, key)
        return (tok + 1) %% logits.shape[-1]
    ServeEngine._sample = sample
    return lambda: setattr(ServeEngine, "_sample", orig)
def state_unchanged():
    orig = ServeEngine._block_impl
    def block(self, plan, params, state, cancel):
        new, toks, emitted = orig(self, plan, params, state, cancel)
        return state, toks, emitted
    ServeEngine._block_impl = block
    return lambda: setattr(ServeEngine, "_block_impl", orig)
ARGV = %r
case("sound", ARGV)
case("token_altered", ARGV, token_altered)
case("state_unchanged", ARGV, state_unchanged)
"""


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_cells.make_checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("lane,script,argv,faults", [
    ("serve", SERVE_FAULTS, SERVE, ["token_altered", "state_unchanged"]),
], ids=["serve"])
def test_planted_faults_are_not_correct(checkout, lane, script, argv,
                                        faults):
    p = tiny_cells.python(checkout, tiny_cells.CASES_SCRIPT
                          + script % (argv,), timeout=900)
    got = tiny_cells.cases(p.stdout)
    assert set(got) == {"sound", *faults}, p.stderr[-3000:]
    assert got["sound"]["rc"] == 0
    assert got["sound"]["result"]["correct"] is True, got["sound"]
    for f in faults:
        res = got[f]["result"]
        assert got[f]["rc"] == 0 and res["correct"] is False, (f, res)
        assert any(c["value"] > c["limit"]
                   for c in res["compared"].values())
