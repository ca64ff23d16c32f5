"""SUBP1 selection and the SUBP2-4 block-coordinate descent of GenFV
(arXiv:2503.19676, Sec. V), in plain numpy.

SUBP1 (eq. 26-30): keep vehicle n iff EMD_n <= EMD_hat and its nominal
delay (one subcarrier, maximum power) fits min(t_hold, t_max).
SUBP2 (Alg. 1, eq. 33-38): subcarriers by projected subgradient ascent on
the KKT multipliers. SUBP3 (Alg. 2, eq. 39-46): power by successive convex
approximation. SUBP4 (eq. 48): b* = floor((t_bar - T_s(b_prev)) / t0).
`dt` is the float type of every array (float64 as the paper's solver runs;
float32 for the control).

`cfg` is a dict of the round loop's GenFVConfig fields; a vehicle is a
dict of x, v, phi_max, f_mem, f_core, v_core, gain_db and emd.
"""
from __future__ import annotations

import numpy as np

# Eq. 6-8 GPU model and the RSU's eq. 13 (Sec. IV-A3, IV-A5).
GPU = dict(t0=0.01, c1=1.0, c2=1.0, theta_mem=2.0e7, theta_core=8.0e7,
           p_g0=5.0, zeta_mem=2.0e-9, zeta_core=8.0e-9)
RSU_F_CORE = 1.5e9
RSU_SPEEDUP = 8.0


def _arr(fleet, key, dt):
    return np.array([v[key] for v in fleet], dt)


def _holding(cfg, x, v):
    half = np.sqrt(cfg["rsu_radius"] ** 2 - cfg["rsu_road_offset"] ** 2)
    s = half - np.sign(v) * x
    return np.maximum(s, 0.0) / np.maximum(np.abs(v) / 3.6, 1e-9)


def _train_times(f_mem, f_core, h):
    g = GPU
    return (g["t0"] + g["c1"] * h * g["theta_mem"] / f_mem
            + g["c2"] * h * g["theta_core"] / f_core)


def _b_prime(cfg, x, gain_db):
    n0 = 10 ** ((cfg["noise_power_dbm"] - 30.0) / 10.0) * cfg["subcarrier_bw"]
    d = np.hypot(x, cfg["rsu_road_offset"])
    return (cfg["unit_channel_gain"] * 10.0 ** (gain_db / 10.0)
            * d ** (-cfg["path_loss_exp"]) / n0)


def select(cfg, fleet, model_bits, h, dt=np.float64):
    """SUBP1 indicator alpha [N]."""
    x, v = _arr(fleet, "x", dt), _arr(fleet, "v", dt)
    t_bar = np.minimum(_holding(cfg, x, v), cfg["t_max"])
    t_cp = _train_times(_arr(fleet, "f_mem", dt), _arr(fleet, "f_core", dt), h)
    snr = _arr(fleet, "phi_max", dt) * _b_prime(cfg, x,
                                                 _arr(fleet, "gain_db", dt))
    rate = cfg["subcarrier_bw"] * np.log2(1.0 + snr)
    t_mu = model_bits / np.maximum(rate, 1e-9)
    emd = _arr(fleet, "emd", dt)
    return ((emd <= cfg["emd_threshold"]) & (t_cp + t_mu <= t_bar)).astype(
        np.int32)


def _project(l, M, l_min):
    pinned = np.zeros(l.shape[0], bool)
    for _ in range(l.shape[0]):
        s_pin = l_min * np.count_nonzero(pinned)
        s_free = l[~pinned].sum()
        if s_pin + s_free <= M:
            break
        l = np.where(pinned, l_min, l * (max(M - s_pin, 0.0)
                                         / max(s_free, 1e-300)))
        newly = ~pinned & (l < l_min)
        if not newly.any():
            break
        pinned |= newly
        l = np.where(pinned, l_min, l)
    return l


def _bandwidth(cfg, A, B, C, D, dt):
    n = A.shape[0]
    M, l_min, step = cfg["num_subcarriers"], cfg["bw_l_min"], cfg["bw_step"]
    lam1, lam2, lam3 = np.ones(n, dt), 1.0, 1.0
    l = np.full(n, M / n, dt)
    prev = l.copy()
    for _ in range(cfg["bw_max_iter"]):
        l = np.sqrt((lam1 * B + lam2 * D) / max(lam3, 1e-9)).astype(dt)
        l = _project(np.clip(l, l_min, M), M, l_min)
        t_bar = np.max(A + B / l)
        lam1 = np.maximum(lam1 + step * (A + B / l - t_bar), 0.0) + 1e-12
        lam2 = max(lam2 + step * (np.sum(C + D / l) - cfg["e_max"] * n),
                   0.0) + 1e-12
        lam3 = max(lam3 + step * (l.sum() - M), 1e-6)
        if np.max(np.abs(l - prev)) < cfg["bw_tol"]:
            break
        prev = l.copy()
    return l


def _power(cfg, model_bits, l_w, bp, G, phi_max, dt):
    phi_min = cfg["phi_min"]
    phi = np.full(l_w.shape[0], phi_min, dt)
    a = model_bits / l_w
    for _ in range(cfg["sca_max_iter"]):
        u = bp * phi
        log2u = np.log2(1.0 + u)
        e_i = phi * (model_bits / (l_w * log2u))
        de = a / log2u - a * bp * phi / (np.log(2.0) * (1.0 + u) * log2u ** 2)
        with np.errstate(divide="ignore", invalid="ignore"):
            phi_b = np.where(de > 1e-12, phi + (cfg["e_max"] - G - e_i) / de,
                             phi_max)
        new = np.clip(np.minimum(phi_b, phi_max), phi_min, phi_max).astype(dt)
        done = np.max(np.abs(new - phi)) < cfg["sca_eps"]
        phi = new
        if done:
            break
    return phi


def _rsu_train_time(batches):
    g = GPU
    return g["t0"] + (g["c1"] * batches * g["theta_mem"]
                      + g["c2"] * batches * g["theta_core"]) / (
        RSU_F_CORE * RSU_SPEEDUP)


def plan(cfg, fleet, alpha, model_bits, h, b_prev, t_image, dt=np.float64):
    """SUBP2-4 for the selected set. Returns l, phi [K], b_gen, t_bar."""
    sel = [v for v, a in zip(fleet, alpha) if a]
    if not sel:
        return dict(l=np.zeros(0), phi=np.zeros(0), b_gen=0, t_bar=0.0)
    x = _arr(sel, "x", dt)
    f_mem, f_core = _arr(sel, "f_mem", dt), _arr(sel, "f_core", dt)
    v_core = _arr(sel, "v_core", dt)
    t_cp = _train_times(f_mem, f_core, h)
    g = GPU
    e_cp = (g["p_g0"] + g["zeta_mem"] * f_mem
            + g["zeta_core"] * v_core ** 2 * f_core) * t_cp
    bp = _b_prime(cfg, x, _arr(sel, "gain_db", dt)).astype(dt)
    phi_max = _arr(sel, "phi_max", dt)
    W, gen_batch = cfg["subcarrier_bw"], cfg["gen_batch"]
    eps = cfg["bcd_eps"]
    l = np.full(len(sel), cfg["num_subcarriers"] / len(sel), dt)
    phi = phi_max.copy()
    b = int(b_prev)
    for _ in range(cfg["bcd_max_iter"]):
        l_old, phi_old, b_old = l, phi, b
        B = model_bits / (W * np.log2(1.0 + bp * phi))
        l = _bandwidth(cfg, t_cp, B, e_cp, phi * B, dt)
        phi = _power(cfg, model_bits, l * W, bp, e_cp, phi_max, dt)
        t_mu = model_bits / (l * W * np.log2(1.0 + bp * phi))
        t_bar = float(np.max(t_cp + t_mu))
        budget = min(t_bar, cfg["t_max"]) - _rsu_train_time(
            max(b_old // gen_batch, 1))
        b = int(np.floor(budget / t_image)) if budget > 0 else 0
        if (np.max(np.abs(l - l_old)) < eps
                and np.max(np.abs(phi - phi_old)) < eps and abs(b - b_old) < 1):
            break
    t_mu = model_bits / (l * W * np.log2(1.0 + bp * phi))
    return dict(l=l, phi=phi, b_gen=b, t_bar=float(np.max(t_cp + t_mu)))
