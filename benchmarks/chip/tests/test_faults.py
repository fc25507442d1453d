"""With the timed path broken underneath, the run comes out incorrect:
once for each fault a cell can have. (The cells' shards share nothing,
so there is no exchange between chips to leave out.)"""
import pytest


def _state_unchanged(step):
    def run(banks, z, valid):
        return step(banks, z, valid)._replace(bank=banks)
    return run


def _half_the_lanes(step):
    import jax
    import jax.numpy as jnp

    def run(banks, z, valid):
        res = step(banks, z, valid)
        L = z.shape[0]
        keep = jnp.arange(L) < L // 2

        def sel(new, old):
            ax = 1 if new.ndim == old.ndim and new.shape[0] != L else 0
            shape = [1] * new.ndim
            shape[ax] = L
            return jnp.where(keep.reshape(shape), new, old)

        return res._replace(bank=jax.tree.map(sel, res.bank, banks))
    return run


def _one_answer_altered():
    """The 7th dispatch moves every slot's x by 0.5 m (the front end asks
    for the step afresh per dispatch, so the count lives out here)."""
    calls = []

    def wrap(step):
        def run(banks, z, valid):
            res = step(banks, z, valid)
            calls.append(1)
            if len(calls) == 7:
                return res._replace(bank=res.bank._replace(
                    x=res.bank.x.at[..., 0].add(0.5)))
            return res
        return run
    return wrap


@pytest.mark.parametrize("name", ["mot17-lkf.cams30", "mot20-imm.cams25"])
@pytest.mark.parametrize("fault", [lambda: _state_unchanged,
                                   lambda: _half_the_lanes,
                                   _one_answer_altered],
                         ids=["state_unchanged", "half_the_lanes",
                              "one_answer_altered"])
def test_live_fault_is_caught(run_tiny, name, fault):
    out = run_tiny(name, wrap=fault())
    assert not out["correct"], out["compared"]
