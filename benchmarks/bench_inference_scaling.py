"""Paper Figs. 7/8: ML-inference task scaling.

Fig. 7: molecule evaluations/second vs number of (thread) workers.
Fig. 8: result-transfer time (worker -> thinker) with vs without the Value
Server as worker count grows -- the paper's point is that the VS keeps
transfer time flat because large results stop flowing through the queue
path.

Simulation caveat: workers are threads on
one CPU, so Fig. 7 cannot show real multi-node speedup; the *relative*
VS-vs-no-VS transfer behaviour (Fig. 8) is the reproducible claim.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.apps.synapp import SynConfig, run_synapp
from repro.configs import mpnn_surrogate
from repro.data import molecules


def inference_rate(n_molecules: int = 512) -> float:
    """Molecules/second through the (jitted) MPNN ensemble, CPU."""
    from repro.apps.electrolyte import Surrogate
    cfg = mpnn_surrogate.reduced()
    s = Surrogate(cfg)
    space = molecules.MoleculeSpace(num_molecules=n_molecules)
    feats = jax.tree.map(jax.numpy.asarray,
                         molecules.featurize(space, range(n_molecules)))
    s.predict(feats)                       # compile
    t0 = time.perf_counter()
    s.predict(feats)
    dt = time.perf_counter() - t0
    return n_molecules / dt


def run(T: int = 60, result_mb: float = 1.0, workers=(1, 2, 4, 8)):
    rows = [("fig7_inference_rate_mol_per_s", inference_rate(), "jit, CPU")]
    O = int(result_mb * (1 << 20))
    for N in workers:
        for use_vs in (False, True):
            res = run_synapp(SynConfig(T=T, D=0.01, I=1 << 10, O=O, N=N,
                                       use_value_server=use_vs))
            transfer = res["medians"].get("result_queue_transit", 0.0) + \
                res["medians"].get("serialize_result", 0.0)
            tag = "vs" if use_vs else "novs"
            rows.append((f"fig8_result_transfer_us_{tag}_N={N}",
                         transfer * 1e6, ""))
    return rows


if __name__ == "__main__":
    for name, val, extra in run():
        print(f"{name},{val:.1f},{extra}")
