"""MD17 example: energy + force training through the columnar dataset
format (reference: examples/md17/md17.py — MD17 aspirin energy training;
extended here to the energy+force objective the MD17 benchmark is actually
scored on, via ``compute_grad_energy`` second-order AD).

The real MD17 download is unavailable in this image (zero egress), so the
dataset builder takes one of two sources:

- ``--xyz_dir DIR``: a directory of .xyz files (real MD17 frames; comment
  line = energy, columns 5-7 = forces), parsed by the raw XYZ loader, or
- the default MD17-*shaped* generator (``md17_shaped_dataset``): thermal
  perturbations of a fixed 21-atom aspirin-composition molecule with
  physically-consistent energies/forces.

Either source is written once through ``ColumnarWriter`` and read back via
``Dataset.format: "columnar"``. Prints the test-set force MAE — the
PERF.md "MD17-shaped force MAE" row.

    python examples/md17/md17.py [--mpnn_type SchNet] [--num_samples 512]
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import hydragnn_tpu
from hydragnn_tpu.data import ColumnarWriter, md17_shaped_dataset
from hydragnn_tpu.data.raw import finalize_graphs, load_xyz_file

_HERE = os.path.dirname(os.path.abspath(__file__))

# bump when md17_shaped_dataset's distribution changes (v2 = the round-5
# Boltzmann-style force-cap acceptance): a stale shard must not silently
# produce numbers that don't correspond to the PERF.md recipe
_GEN_VERSION = 2


def _shard_meta(path):
    metas = sorted(glob.glob(os.path.join(path, "shard*", "meta.json")))
    if not metas:
        return {}
    with open(metas[0]) as fh:
        return json.load(fh)


def build_dataset(path, num_samples, radius, max_neighbours, xyz_dir=None):
    """Write the columnar shard once; later runs reuse it (synthetic shards
    are regenerated when the generator version or sample count changed)."""
    if os.path.isdir(path):
        if xyz_dir:
            print(f"reusing existing shard at {path}")
            return
        meta = _shard_meta(path)
        if (
            meta.get("num_samples") == num_samples
            and meta.get("attrs", {}).get("md17_gen_version") == _GEN_VERSION
        ):
            print(f"reusing {num_samples}-sample v{_GEN_VERSION} shard at {path}")
            return
        import shutil

        print(
            f"regenerating {path}: existing shard is "
            f"v{meta.get('attrs', {}).get('md17_gen_version')} with "
            f"{meta.get('num_samples')} samples, want v{_GEN_VERSION} with "
            f"{num_samples}"
        )
        shutil.rmtree(path)
    if xyz_dir:
        graphs = []
        for f in sorted(glob.glob(os.path.join(xyz_dir, "*.xyz"))):
            g = load_xyz_file(f)
            # columns after x,y,z are forces; comment line is the energy
            if g.x.shape[1] < 4:
                raise ValueError(
                    f"{f}: expected 'Symbol x y z fx fy fz' rows (3 force "
                    f"columns after the position); found {g.x.shape[1] - 1} "
                    "extra column(s)"
                )
            if g.graph_y is None or len(g.graph_y) < 1:
                raise ValueError(f"{f}: comment line must carry the energy value")
            g.node_targets = {"forces": np.asarray(g.x[:, 1:4], np.float32)}
            g.graph_targets = {"energy": np.asarray(g.graph_y[:1], np.float32)}
            g.x = g.x[:, :1]
            g.graph_y = None
            graphs.append(g)
        graphs = finalize_graphs(graphs, radius=radius, max_neighbours=max_neighbours)
    else:
        graphs = md17_shaped_dataset(
            number_configurations=num_samples,
            radius=radius,
            max_neighbours=max_neighbours,
        )
    writer = ColumnarWriter(path).add(graphs)
    if not xyz_dir:
        writer.add_global("md17_gen_version", _GEN_VERSION)
    writer.save()
    print(f"wrote {len(graphs)} samples -> {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mpnn_type", default=None)
    ap.add_argument("--num_epoch", type=int, default=None)
    ap.add_argument("--num_samples", type=int, default=512)
    ap.add_argument("--xyz_dir", default=None, help="optional real-data xyz directory")
    args = ap.parse_args()

    with open(os.path.join(_HERE, "md17.json")) as f:
        config = json.load(f)
    arch = config["NeuralNetwork"]["Architecture"]
    if args.mpnn_type:
        arch["mpnn_type"] = args.mpnn_type
    if args.num_epoch:
        config["NeuralNetwork"]["Training"]["num_epoch"] = args.num_epoch

    data_path = os.path.join(os.getcwd(), config["Dataset"]["path"]["total"])
    config["Dataset"]["path"]["total"] = data_path
    build_dataset(
        data_path, args.num_samples, arch["radius"], arch["max_neighbours"],
        xyz_dir=args.xyz_dir,
    )

    model, state, hist, config, loaders, mm = hydragnn_tpu.run_training(config)
    tot, tasks, preds, trues = hydragnn_tpu.run_prediction(config, model_state=state)
    force_mae = float(np.mean(np.abs(preds["forces"] - trues["forces"])))
    energy_mae = float(np.mean(np.abs(preds["graph_energy"] - trues["graph_energy"])))
    # NaN-safe: a degenerate run predicting constant forces has zero
    # variance and np.corrcoef would print "corr nan", breaking the
    # regression test's parse exactly when it should fail on the bound
    pf, tf = preds["forces"].ravel(), trues["forces"].ravel()
    if pf.std() > 0 and tf.std() > 0:
        force_corr = float(np.corrcoef(pf, tf)[0, 1])
    else:
        force_corr = 0.0
    # trivial-predictor baselines: any committed number must be read against
    # these (zero force / test-mean energy), so a run that learned nothing
    # cannot masquerade as a measurement
    zero_force_mae = float(np.mean(np.abs(trues["forces"])))
    mean_energy_mae = float(
        np.mean(np.abs(trues["graph_energy"] - trues["graph_energy"].mean()))
    )
    print(
        f"test loss {tot:.5f}; energy MAE {energy_mae:.5f} "
        f"(test-mean predictor {mean_energy_mae:.5f}); "
        f"force MAE {force_mae:.5f} (zero predictor {zero_force_mae:.5f}, "
        f"corr {force_corr:.3f})"
    )


if __name__ == "__main__":
    main()
