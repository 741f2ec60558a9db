"""The plain reference of ``fit``'s first steps: its rotation, its rate
schedule, and the numbers it compares, on hand-made values."""

import jax.numpy as jnp
import numpy as np
import pytest

import fit_reference as fr


def test_rotation_is_orthonormal_and_identity_at_zero():
    assert np.array_equal(np.asarray(fr.rotation(jnp.zeros(6), 4)),
                          np.eye(4))
    theta = jnp.asarray(np.random.default_rng(0).normal(size=6), jnp.float32)
    r = np.asarray(fr.rotation(theta, 4), np.float64)
    np.testing.assert_allclose(r @ r.T, np.eye(4), atol=1e-5)
    assert np.abs(r - np.eye(4)).max() > 0.1


@pytest.mark.parametrize("step", [0, 1, 299, 300, 749, 1000])
def test_one_cycle_matches_the_program(step):
    from repro.common.optim import one_cycle

    assert fr.one_cycle(step, 1e-3, 750) == pytest.approx(
        float(one_cycle(1e-3, 750)(step)), rel=1e-6, abs=1e-9)


def _steps(scale=1.0):
    leaf = lambda n: {"theta": np.full(3, n), "codebooks": np.full(4, n),  # noqa: E731
                      "log_alpha": np.full((), n)}
    return [fr.SimpleNamespace(loss=2.0, grad=leaf(0.5 * scale),
                               params=leaf(0.01 * scale))]


def test_numbers_read_nought_for_the_reference_itself():
    ref = _steps()
    program = {"params0": {k: np.zeros_like(v) for k, v in
                           ref[0].params.items()},
               "params": ref[0].params, "losses": [2.0],
               "grad": ref[0].grad}
    assert fr.numbers(program, ref) == {"fit_loss_rel_gap": 0.0,
                                        "fit_grad_gap": 0.0,
                                        "fit_change_gap": 0.0}


def test_numbers_read_one_for_a_state_left_unchanged():
    ref = _steps()
    zeros = {k: np.zeros_like(v) for k, v in ref[0].params.items()}
    program = {"params0": zeros, "params": zeros, "losses": [2.0],
               "grad": zeros}
    nums = fr.numbers(program, ref)
    assert nums["fit_grad_gap"] == 1.0 and nums["fit_change_gap"] == 1.0


def test_a_still_leaf_is_left_out_of_the_change():
    ref = _steps()
    ref[0].grad["log_alpha"] = np.full((), 1e-9)
    zeros = {k: np.zeros_like(v) for k, v in ref[0].params.items()}
    params = dict(ref[0].params, log_alpha=np.full((), 5.0))
    program = {"params0": zeros, "params": params, "losses": [2.0],
               "grad": ref[0].grad}
    assert fr.numbers(program, ref)["fit_change_gap"] == 0.0


def test_what_is_not_finite_fails():
    ref = _steps()
    ref.append(ref[0])
    grad = dict(ref[0].grad, codebooks=np.full(4, np.nan))
    program = {"params0": {k: np.zeros_like(v) for k, v in
                           ref[0].params.items()},
               "params": ref[0].params, "losses": [2.0, float("nan")],
               "grad": grad}
    nums = fr.numbers(program, ref)
    assert nums["fit_loss_rel_gap"] == fr.NOT_FINITE
    assert nums["fit_grad_gap"] == fr.NOT_FINITE
