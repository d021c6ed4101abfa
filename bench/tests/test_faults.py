"""A run with the timed path broken underneath must come out not correct.

Each case drives a whole run on the CPU at tiny sizes (the look for a
chip is skipped), with one fault planted in the program by monkeypatch:

* state unchanged: every transition returns the state it was given;
* half of the batch: the log likelihood over half the rows, doubled;
* half of the fleet: half the chains computed and each returned twice;
* the gradient in a lower precision: the sampler's gradient rounded to
  bfloat16, its value left as it is (NUTS stays exact under any
  reversible force field, so only the gradient's check sees it);
* answer altered where produced: every draw moved by 0.05 as the program
  packages it.

There is one chip per cell, so the exchange between chips has no fault to
plant. A sound run of each cell must come out correct.
"""
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

import run
from tiny import shrink


@pytest.fixture(autouse=True)
def fresh_programs(tmp_path, monkeypatch):
    from repro.core.program import clear_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clear_cache()
    yield
    clear_cache()


def _run(cell: str, seconds: str = "3") -> dict:
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", cell, "--seed", "4000000007",
                         "--seconds", seconds, "--trace", "0"],
                        require_chip=False, shrink=shrink) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _state_unchanged(monkeypatch):
    from repro.infer import chains
    orig = chains._chain_body

    def body(kern, num_warmup, num_samples):
        def step(state, key):
            _, out = kern.step(state, key)
            return state, dict(out, q=state[0], logp=state[1])
        return orig(kern._replace(step=step, warm=lambda s, t, k: s),
                    num_warmup, num_samples)
    monkeypatch.setattr(chains, "_chain_body", body)


def _logreg_half_rows(monkeypatch):
    from repro.core import program
    from repro.core.contexts import LikelihoodContext, PriorContext

    def density_program(model, tvi, ctx=None, backend="fused"):
        half = model.bind(X=model.data["X"][::2], y=model.data["y"][::2])

        def ld(q):
            t = tvi.replace_flat(q)
            return (model.logp_with_context(t, PriorContext(),
                                            backend=backend)
                    + 2.0 * half.logp_with_context(t, LikelihoodContext(),
                                                   backend=backend))
        return ld
    monkeypatch.setattr(program, "density_program", density_program)


def _half_chains_twice(monkeypatch):
    from repro.infer import chains
    orig = chains.package_draws

    def twice(a):
        a = np.asarray(a)
        h = a.shape[0] // 2
        return np.concatenate([a[:h], a[:h], a[2 * h:]])

    monkeypatch.setattr(
        chains, "package_draws",
        lambda tvi, qs, stats=None: orig(
            tvi, twice(qs), stats={k: twice(v) for k, v in stats.items()}))


def _grad_bfloat16(monkeypatch):
    import jax.numpy as jnp

    from repro.infer.nuts import NUTS
    orig = NUTS._make_ld_grad

    def make(self, logdensity, spec, spec_reason=None):
        f = orig(self, logdensity, spec, spec_reason)

        def ld_grad(q):
            lp, g = f(q)
            return lp, g.astype(jnp.bfloat16).astype(g.dtype)
        return ld_grad
    monkeypatch.setattr(NUTS, "_make_ld_grad", make)


def _draws_altered(monkeypatch):
    from repro.infer import chains
    orig = chains.package_draws
    monkeypatch.setattr(chains, "package_draws",
                        lambda tvi, qs, stats=None: orig(tvi, qs + 0.05,
                                                         stats=stats))


FAULTS = [(cell, fault)
          for cell in ("logreg.fleet", "logreg.stan4")
          for fault in (_state_unchanged, _logreg_half_rows,
                        _half_chains_twice, _grad_bfloat16, _draws_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(cell)
    failing = [n for n, c in result["checks"].items()
               if not c["value"] <= c["limit"]]
    assert result["correct"] is False and failing, result["checks"]


@pytest.mark.parametrize("cell", ["logreg.fleet", "logreg.stan4"])
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"] is True, result["checks"]
