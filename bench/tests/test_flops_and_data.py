"""The operation count, the peak table and the seeded data."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.data import data_for, seed_key


def gp_step_model_flops(n, d, s, epochs):
    # The formula of src/repro/distributed/gp_step.py (MODEL_FLOPS of the
    # GP cell), as written there.
    per_entry = 3 * d + 8 + 2 * (1 + s)
    return float(n) * n * per_entry * (epochs + 2)


@pytest.mark.parametrize("n,d,s,epochs", [(12150, 26, 64, 3), (20480, 3, 64, 10),
                                          (1024, 90, 8, 0)])
def test_step_flops_is_the_gp_step_formula(n, d, s, epochs):
    assert flops.step_flops(n, d, s, epochs) == gp_step_model_flops(
        n, d, s, epochs)


def test_peak_table():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")


CONFIG = {"n_train": 300, "d": 4, "data": {"seed": 0,
                                           "lengthscale_per_sqrt_d": 1.6,
                                           "noise": 0.1, "num_features": 64,
                                           "box": 2.0}}


def rows(x, y):
    """The rows as a set: sorted lexicographically."""
    a = np.concatenate([np.asarray(x), np.asarray(y)[:, None]], axis=1)
    return a[np.lexsort(a.T[::-1])]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_draw(seed):
    x1, y1 = data_for(CONFIG, seed)
    x2, y2 = data_for(CONFIG, seed)
    assert x1.shape == (300, 4) and y1.shape == (300,)
    assert x1.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert abs(float(y1.mean())) < 1e-5 and abs(float(y1.std()) - 1) < 1e-4


@pytest.mark.parametrize("other", [6, 5 + 2**32])
def test_other_seeds_order_the_same_rows_differently(other):
    x1, y1 = data_for(CONFIG, 5)
    x2, y2 = data_for(CONFIG, other)
    assert not np.array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(rows(x1, y1), rows(x2, y2))


def test_the_dataset_seed_changes_the_data():
    other = dict(CONFIG, data=dict(CONFIG["data"], seed=1))
    x1, y1 = data_for(CONFIG, 5)
    x2, y2 = data_for(other, 5)
    assert not np.allclose(rows(x1, y1), rows(x2, y2))
    with pytest.raises(ValueError):
        seed_key(-1)


def test_shares_of_peak_count_the_cells_chips():
    from types import SimpleNamespace

    from bench.spec import load_reader

    read = load_reader("fit_mfu", "layer_metrics")
    config = {"n_train": 20480, "d": 3, "num_probes": 64}
    window = SimpleNamespace(seconds=41.5, histories=[
        {"epochs": np.full(100, 10.0)}] * 4)

    def fit_mfu(chips):
        return read({"window": window, "device_kind": "TPU v5 lite",
                     "cell": SimpleNamespace(config=config, chips=chips)})

    assert fit_mfu(4) == pytest.approx(fit_mfu(1) / 4, rel=1e-12)
    assert fit_mfu(1) == pytest.approx(
        100 * 400 * flops.step_flops(20480, 3, 64, 10.0) / 41.5 / 197e12)
