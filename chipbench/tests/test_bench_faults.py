"""The check fails what it must: the float8 control in the program's
place, and the timed path broken underneath a whole run.

Limits here are for the smoke size, set like the cells' are: on the CPU
at this size, with samples of at least 120 served tokens from 6 s
windows, sound runs read a widest gap of at most 0.0067 and the float8
control at least 0.057 (seeds 0-3, 7 and 2**31 + 2 of both mixes), so
0.02 lies between them with room on both sides."""

import jax
import pytest

import chipbench.system as system
from chipbench import calibrate, run, spec
from chipbench.tests import smoke

LIMIT = 0.02


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    yield smoke.make_root(tmp_path, limit=LIMIT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)


@pytest.mark.parametrize("cell", ["smoke.chat", "smoke.long-prompt"])
def test_control_reads_over_the_limit(root, cell):
    """The reference in float8, in the program's place, on three seeds:
    the gap of its top token passes the limit on each; the program's own
    reading of the same sample stays under it."""
    cal = calibrate.Calibration(spec.load_cell(cell, root))
    for seed in (0, 1, 2**31 + 2):
        r = cal.read(seed, 6.0)  # long enough to finish a full sample on a busy CPU
        assert r["sampled_tokens"] >= 120, r
        assert r["control_gap"] > LIMIT > r["program_gap"], r


def _break(monkeypatch, fault):
    init = system.ServeLoop.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        step, vocab = self._decode_arena, self.cfg.vocab_size
        if fault == "state_unchanged":  # the step hands back the arena it was given
            self._decode_arena = lambda p, c, t, act: (step(p, c, t, act)[0], c)
        else:  # each decoded token altered where the step produces it
            def altered(p, c, t, act):
                toks, arena = step(p, c, t, act)
                return (toks + 1) % vocab, arena
            self._decode_arena = altered

    monkeypatch.setattr(system.ServeLoop, "__init__", broken_init)


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cell", ["smoke.chat", "smoke.long-prompt"])
def test_broken_step_reads_not_correct(root, monkeypatch, cell, fault):
    _break(monkeypatch, fault)
    res = run.main(["--workload", cell, "--seed", "7", "--seconds", "4", "--trace", "0"],
                   root=root, chip_check=smoke.cpu_chip)
    assert res["correct"] is False
    assert res["check"]["max_logit_gap_at_most"]["value"] > LIMIT
