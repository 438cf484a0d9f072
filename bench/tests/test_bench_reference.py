"""The plain reference against the program at reduced sizes on the CPU."""

import jax
import numpy as np
import pytest

from bench import check, families, reference


def test_resnet_reference_losses_match_program():
    """The reference's PreActResNet losses equal the program's on the same
    weights and a small batch (8x8 images keep it quick)."""
    from bench.run import load_cell
    cell = load_cell("preact18-gn.paper-f32")
    cfg = dict(cell["cfg"], image_size=8)
    adapter, w = families.weights(cfg, 7)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {"images": jax.random.normal(k1, (2, 8, 8, 3)),
             "labels": jax.random.randint(k2, (2,), 0, 10)}
    num = reference.Numerics("float32", "stated")
    simple, side = reference.family(cfg).losses(cfg, num)
    with jax.default_matmul_precision("highest"):
        got = (float(simple(w, batch)), float(side(w, batch)))
        want = (float(adapter.loss_simple(w, batch)),
                float(adapter.loss_side(w, batch)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_cohort_matches_program_sampler():
    from repro.core.sampling import CohortSampler
    traffic = {"clients": 100, "simple_clients": 50, "participation": 0.1}
    sampler = CohortSampler(n_devices=100, n_simple=50, participation=0.1,
                            seed=2_147_483_659)
    for r in range(4):
        plan = sampler.plan(r)
        ids_s, ids_c = reference.cohort(traffic, 2_147_483_659, r)
        np.testing.assert_array_equal(plan.simple_ids, ids_s)
        np.testing.assert_array_equal(plan.complex_ids, ids_c)


def test_control_precision_is_one_step_down():
    """The control stores float32 parameters and computes in bfloat16, and
    there is no control below a bfloat16 configuration."""
    num = reference.Numerics("float32", "control")
    assert num.store == np.dtype("bfloat16") and num.act == np.dtype(
        "bfloat16")
    assert reference.Numerics("float32", "stated").store == np.float32
    with pytest.raises(ValueError):
        reference.Numerics("bfloat16", "control")


def test_control_fails_the_comparison(tiny):
    """The control, put in the program's place, comes out not correct
    through the harness's own comparison and limits."""
    w0, ref_models, ref_losses = check.reference_rounds(tiny, 5, 2)
    _, ctl_models, ctl_losses = check.reference_rounds(tiny, 5, 2,
                                                       "control")
    got = check.numbers(w0, ctl_models, ctl_losses, ref_models, ref_losses, 2)
    assert not check.correct(check.judged(tiny, got)), got
