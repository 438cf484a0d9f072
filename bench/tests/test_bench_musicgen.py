"""The MusicGen family at a reduced width on the CPU: the program's three
client objectives against the plain reference, a whole tiny federated run
against the reference rounds, the control failing them, the FLOP count
against XLA's, and the configuration file against the program's registry
entry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, families, reference, run
from conftest import TINY_LIMITS, TINY_TRAFFIC

CELL = "musicgen-large-d4.silo-f32"
# every width cut, the structure kept: 4 codebooks in the delay pattern,
# cross-attention to padded conditioning, a half-depth exit
SMALL = {"n_layers": 2, "exit_layer": 1, "d_model": 32, "n_heads": 4,
         "head_dim": 8, "d_ff": 64, "card": 16, "n_codebooks": 4,
         "crop_frames": 10, "seq_len": 16, "cond_tokens": 5, "cond_dim": 12}


def small_cfg(**kw) -> dict:
    cfg = dict(run.load_cell(CELL)["cfg"], **dict(SMALL, **kw))
    adapter = families.family(cfg).adapter(cfg)
    shapes = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    cfg["n_params"] = sum(int(np.prod(s.shape))
                          for s in jax.tree.leaves(shapes))
    return cfg


def small_cell() -> dict:
    cell = run.load_cell(CELL)
    return {"name": "tiny-musicgen", "cfg": small_cfg(),
            "traffic_data": dict(TINY_TRAFFIC, dirichlet_alpha=0,
                                 lr=0.05, clip_norm=1.0),
            "limits": TINY_LIMITS, "per_layer": [],
            "end_to_end": cell["end_to_end"]}


def _batch(cfg, n=2):
    traffic = dict(TINY_TRAFFIC, clients=1, points_per_client=n)
    return families.family(cfg).data(cfg, traffic, jax.random.PRNGKey(3))[0]


def test_program_losses_match_reference():
    """loss_simple, loss_side and loss_complex equal the reference's at
    ``highest`` on seeded weights, with padded conditioning and the
    delay pattern's special tokens in the labels."""
    cfg = small_cfg()
    adapter, w = families.weights(cfg, 7)
    batch = _batch(cfg)
    assert not bool(jnp.all(batch["cond_mask"]))      # padding present
    num = reference.Numerics("float32", "highest")
    simple, side = reference.family(cfg).losses(cfg, num)
    with jax.default_matmul_precision("highest"):
        got = [float(f(w, batch)) for f in (adapter.loss_simple,
                                            adapter.loss_side,
                                            adapter.loss_complex)]
        want = [float(simple(w, batch)), float(side(w, batch))]
        # the complex loss alone: the side objective less the exit head's
        want.append(want[1] - float(simple(w, batch)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_tiny_run_matches_reference_and_control_fails():
    """A tiny federated run through the benchmark's own set-up path
    agrees with the reference rounds; the control (bfloat16) put in the
    program's place does not."""
    cell = small_cell()
    result = run.run_cell(cell, 3_000_000_019, 0.2, False)
    assert result["correct"], result["checks"]
    w0, ref_models, ref_losses = check.reference_rounds(cell, 5, 2)
    _, ctl_models, ctl_losses = check.reference_rounds(cell, 5, 2,
                                                       "control")
    got = check.numbers(w0, ctl_models, ctl_losses, ref_models, ref_losses, 2)
    assert not check.correct(check.judged(cell, got)), got


def test_client_flops_match_xla():
    """``client_flops`` counts one crop's training FLOPs within 5% of
    XLA's count of a compiled step, for each objective.  One layer: XLA
    counts a loop's body once, whatever its trip count."""
    from test_bench_flops import _xla_flops
    cfg = small_cfg(n_layers=1, d_model=256, head_dim=64, d_ff=1024,
                    card=256,
                    seq_len=64, crop_frames=56, cond_tokens=8,
                    cond_dim=64)
    fam = families.family(cfg)
    adapter = fam.adapter(cfg)
    params = jax.eval_shape(adapter.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: _batch(cfg))
    for loss, simple in ((adapter.loss_side, False),
                         (adapter.loss_simple, True)):
        got = 2 * fam.client_flops(cfg, simple)
        want = _xla_flops(jax.grad(loss), params, batch)
        assert got == pytest.approx(want, rel=0.05)


def test_configuration_is_the_registry_entry_cut_in_depth():
    """The file's widths are the program's published ones; only the depth
    (and the exit at half of it) is cut, and the file's n_params is what
    the program builds."""
    from repro import configs
    cfg = run.load_cell(CELL)["cfg"]
    full = configs.get_config(cfg["program_config"])
    cut = families.family(cfg).model_config(cfg)
    assert cut == full.with_overrides(
        n_layers=cfg["n_layers"], exit_layer=cfg["exit_layer"],
        frontend=cut.frontend, param_dtype="float32",
        compute_dtype="float32")
    assert cut.frontend.n_tokens == 64 and cut.frontend.d_in == 768
    assert cfg["reduced"] == ["n_layers"]
    assert cut.param_count() == cfg["n_params"] == 303_630_336
    assert full.param_count() == cfg["published"]["n_params"]
    assert cfg["n_layers"] / full.n_layers == \
        cfg["exit_layer"] / full.exit_layer
