"""The check fails where it must, at tiny sizes on the CPU.

* The control (the reference with int4 weights, its picks put in the
  program's place) comes out not correct by the harness's own
  ``correct_from``, where sound runs of the program come out correct.
* Faults planted in the timed path underneath a whole run make
  ``correct`` false: a decode step that returns its state unchanged, a
  decode step that leaves half of the batch's rows uncomputed, and a
  token altered where the decode step produces it. (One chip: there is
  no exchange between chips to leave out.)
"""

import pytest

import bench_tiny

pytestmark = pytest.mark.usefixtures("tiny_compile_cache")


@pytest.mark.parametrize("cell", ["phi3m-overload"])
@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_and_program_passes(cell, seed):
    res = bench_tiny.run(cell, seed, control=True)
    c, cc = res["checks"], res["control_checks"]
    assert res["correct"], c
    assert c["max_logit_gap"]["value"] <= bench_tiny.TINY_LIMIT
    # the control's picks in the program's place, judged by the harness
    assert not res["control_correct"], cc
    assert cc["max_logit_gap"]["value"] > bench_tiny.TINY_LIMIT
    assert not bench_tiny.harness.correct_from(cc)


def _state_unchanged(step):
    def broken(params, tok, cache, *rest):
        toks, _, keys, bad = step(params, tok, cache, *rest)
        return toks, cache, keys, bad
    return broken


def _half_batch(step):
    def broken(params, tok, cache, *rest):
        toks, cache, keys, bad = step(params, tok, cache, *rest)
        half = toks.shape[0] // 2          # the slots filled first
        return toks.at[:half].set(tok[:half]), cache, keys, bad
    return broken


def _token_altered(step):
    def broken(params, tok, cache, *rest):
        toks, cache, keys, bad = step(params, tok, cache, *rest)
        return (toks + 1) % 512, cache, keys, bad
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_faults_make_the_run_incorrect(fault, monkeypatch):
    from repro.serve.engine import ServeEngine
    post_init = ServeEngine.__post_init__

    def patched(self):
        post_init(self)
        self._step_paged = fault(self._step_paged)

    monkeypatch.setattr(ServeEngine, "__post_init__", patched)
    res = bench_tiny.run("phi3m-overload", 1)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > bench_tiny.TINY_LIMIT
