"""Synthetic workunit fixtures: small time series with injected binary-pulsar
signals, exercising the same math as the 2^22-sample production WUs at test
sizes."""

from __future__ import annotations

import numpy as np

from boinc_app_eah_brp_tpu.io.templates import TemplateBank


def synthetic_timeseries(
    n: int,
    tsample_us: float = 500.0,
    f_signal: float = 37.0,
    P_orb: float = 0.0,
    tau: float = 0.0,
    psi0: float = 0.0,
    amp: float = 6.0,
    noise: float = 1.0,
    seed: int = 0,
    quantize_4bit: bool = True,
) -> np.ndarray:
    """Pulsed signal with optional orbital Doppler modulation + noise,
    quantized to the 4-bit range like real workunit data."""
    rng = np.random.default_rng(seed)
    dt = tsample_us * 1e-6
    t = np.arange(n) * dt
    if P_orb > 0.0:
        # Construct the detector series consistently with the demodulator's
        # model y[i] = x[round(i - del_t[i])]: pulsar-time sample i lands at
        # detector index f(i) = i - del_t[i]; invert f by interpolation to
        # find the pulsar time observed at each detector sample.
        i_idx = np.arange(n, dtype=np.float64)
        del_t = (tau * np.sin(2 * np.pi / P_orb * t + psi0) - tau * np.sin(psi0)) / dt
        t_pulsar = np.interp(i_idx, i_idx - del_t, i_idx) * dt
    else:
        t_pulsar = t
    pulse = amp * (np.cos(2 * np.pi * f_signal * t_pulsar) > 0.95)
    x = pulse + rng.normal(4.0, noise, size=n)
    if quantize_4bit:
        x = np.clip(np.round(x), 0, 15)
    return x.astype(np.float32)


def small_bank(P_true: float = 2.1, tau_true: float = 0.05, psi_true: float = 1.0):
    """A few templates bracketing the injected orbit, plus the null template.

    Orbit periods are of the order of the (tiny) fixture observation time so
    the Doppler modulation genuinely smears/recovers spectral power — the
    same regime as production WUs where t_obs ~ 275 s vs P_orb ~ hours is
    scaled down to t_obs ~ 4 s vs P_orb ~ 2 s."""
    P = [1000.0, P_true, P_true * 1.07, 1.7]
    tau = [0.0, tau_true, tau_true * 0.8, 0.08]
    psi = [0.0, psi_true, psi_true + 0.4, 2.5]
    return TemplateBank(
        np.asarray(P, dtype=np.float64),
        np.asarray(tau, dtype=np.float64),
        np.asarray(psi, dtype=np.float64),
    )


def one_bank_step(geom, ts_args, params, allow_pallas: bool = True):
    """Host (M, T) after one ``make_bank_step`` over ``params`` (one
    ``template_params_host`` tuple per template) as a single batch, from a
    fresh state: the step donates (M, T), so no two calls share one."""
    import jax.numpy as jnp

    from boinc_app_eah_brp_tpu.models.search import (
        init_state,
        make_bank_step,
        upload_bank,
    )

    B = len(params)
    bank = upload_bank(
        tuple(np.array([p[i] for p in params], dtype=np.float32)
              for i in range(4)),
        B,
    )
    step = make_bank_step(geom, B, allow_pallas=allow_pallas)
    M, T = step(ts_args, *bank, jnp.int32(0), jnp.int32(B), *init_state(geom))
    return np.asarray(M), np.asarray(T)
