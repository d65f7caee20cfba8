"""The comparison that decides ``correct`` for a fit cell.

Each fit that the window completed returns its final state: the last
solutions V = [v_y | v_1..v_s] (the warm-start carry), the probes' base
draws and Adam's moments; and per step the hyperparameters, the residuals
and the gradient norm the program reports. For every such fit the plain
reference (``bench.reference``) checks five numbers:

* ``solve_gap``: the program's reported relative residual of the last
  mean-system solve against the true one, ||y - H v_y|| / ||y|| at the
  hyperparameters that solve used, as an absolute difference;
* ``probe_gap``: the same for the probe solves, the reported ``res_z``
  (the mean over probes of the relative residual) against the mean of the
  true ||b_j - H v_j|| / ||b_j||, with b_j rebuilt by the reference from
  the probes' base draws (data fed to both sides);
* ``grad_gap``: the program's reported norm of the last step's gradient
  against the norm of the reference's gradient from the same V, relative to
  the latter;
* ``adam_gap``: the last two hyperparameter updates, coordinate by
  coordinate, against Adam's update rule: step T from the final moments,
  step T-1 from the moments that the reference's gradient of step T
  unwinds them to (m_{T-1} = (m_T + (1 - b1) g) / b1 for ascent, likewise
  the second moment). In units of the learning rate, the worst coordinate.
  A flipped sign, a wrong moment or bias correction, or hyperparameters
  frozen while the moments move, all read of the order of 0.1 to 1;
* ``step1_gap``: the first Adam step from the configuration's initial
  hyperparameters must move every raw hyperparameter by the learning rate
  (Adam's first step is lr * g / |g|), relative to the learning rate.

Each number is the worst over the fits, compared with the cell's limit.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


@dataclass
class Compared:
    """One number compared with its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclass
class FitOutput:
    """What the check reads of one fit: its history and final state."""

    history: dict
    carry_v: jax.Array  # (n, 1 + s)
    rff_z: jax.Array  # (m, d) probes' spectral directions
    rff_u: jax.Array  # (m,) spectral mixture draws
    rff_w: jax.Array  # (2m, s) feature weights
    w_eps: jax.Array  # (n, s) base noise draws
    adam_mu: np.ndarray  # (d + 2,) first moment, flat like the hypers
    adam_nu: np.ndarray  # (d + 2,) second moment


def initial_theta(config: dict) -> np.ndarray:
    init = config["init"]
    d = int(config["d"])
    return np.array([init["lengthscale"]] * d
                    + [init["signal"], init["noise"]], np.float32)


def _raw(theta) -> np.ndarray:
    """Softplus inverse, nu = theta + log(1 - exp(-theta)), in float64."""
    theta = np.asarray(theta, np.float64)
    return theta + np.log(-np.expm1(-theta))


def adam_step(mu, nu, t: int, lr: float, b1: float, b2: float,
              eps: float) -> np.ndarray:
    """Adam's change of the raw hyperparameters at step ``t`` (1-based)
    from that step's moments, in float64."""
    mhat = mu / (1.0 - b1 ** t)
    vhat = np.maximum(nu, 0.0) / (1.0 - b2 ** t)
    return -lr * mhat / (np.sqrt(vhat) + eps)


def adam_gap(hypers, theta0, mu, nu, g_ref, adam: dict) -> float:
    """The worst gap, over coordinates and the last two steps, between the
    program's hyperparameter updates and Adam's rule, over lr."""
    lr, (b1, b2), eps = adam["lr"], adam["betas"], adam["eps"]
    nus = np.concatenate([_raw(theta0)[None], _raw(hypers)])
    t = len(hypers)
    mu, nu = np.float64(mu), np.float64(nu)
    gaps = [np.abs((nus[t] - nus[t - 1])
                   - adam_step(mu, nu, t, lr, b1, b2, eps))]
    if t >= 2:
        g = np.float64(g_ref)  # ascent: the moments hold those of -g
        mu_prev = (mu + (1.0 - b1) * g) / b1
        nu_prev = (nu - (1.0 - b2) * g * g) / b2
        gaps.append(np.abs((nus[t - 1] - nus[t - 2])
                           - adam_step(mu_prev, nu_prev, t - 1, lr, b1, b2,
                                       eps)))
    return float(np.max(np.concatenate(gaps)) / lr)


def adam_settings(config: dict) -> dict:
    b1, b2 = config["adam_betas"]
    return {"lr": float(config["adam_learning_rate"]),
            "betas": (float(b1), float(b2)), "eps": float(config["adam_eps"])}


def fit_numbers(x, y, config: dict, out: FitOutput) -> dict:
    """The five numbers of one fit."""
    kind = config["kernel"]
    adam = adam_settings(config)
    hist = out.history
    hypers = np.asarray(hist["hypers"], np.float32)
    theta0 = initial_theta(config)
    theta_last = jnp.asarray(hypers[-2] if len(hypers) > 1 else theta0)
    v = jnp.asarray(out.carry_v)

    rho_y = float(reference.relative_residual(
        x, theta_last, v[:, :1], y[:, None], kind)[0])
    solve_gap = abs(rho_y - float(hist["res_y"][-1]))

    bf16 = (config["rff_feature_precision"] == "default"
            and x.devices().pop().platform == "tpu")
    b = reference.probe_targets(x, theta_last, out.rff_z, out.rff_u,
                                out.rff_w, out.w_eps, kind, bf16)
    rho_z = float(jnp.mean(reference.relative_residual(
        x, theta_last, v[:, 1:], b, kind)))
    probe_gap = abs(rho_z - float(hist["res_z"][-1]))

    g = np.asarray(reference.raw_gradient(x, theta_last, v, kind))
    g_ref = np.linalg.norm(np.float64(g))
    with np.errstate(divide="ignore", invalid="ignore"):  # 0 reads inf/nan
        grad_gap = float(abs(hist["grad_norm"][-1] - g_ref) / g_ref)

    # On the host in float64: the device's transcendentals would add their
    # own rounding to numbers that only have to tell 1 from 0.
    step1_gap = float(np.max(np.abs(
        np.abs(_raw(hypers[0]) - _raw(theta0)) - adam["lr"])) / adam["lr"])
    return {"solve_gap": solve_gap, "probe_gap": probe_gap,
            "grad_gap": grad_gap,
            "adam_gap": adam_gap(hypers, theta0, out.adam_mu, out.adam_nu,
                                 g, adam),
            "step1_gap": step1_gap}


def compare(x, y, config: dict, fits: list, limits: dict) -> list[Compared]:
    """Worst of each number over ``fits`` (``FitOutput``s) against
    ``limits`` (name -> {"limit": ...}). No fit reads as failed."""
    values: dict[str, list[float]] = {name: [] for name in limits}
    for out in fits:
        numbers = fit_numbers(x, y, config, out)
        for name in limits:
            values[name].append(numbers[name])

    def worst(vals):
        # A non-finite reading, or no reading at all, fails.
        if not vals or not np.all(np.isfinite(vals)):
            return float("nan")
        return float(np.max(vals))

    return [Compared(name, worst(values[name]), float(spec["limit"]))
            for name, spec in limits.items()]
