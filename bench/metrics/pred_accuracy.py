"""Geometric mean, over the held-out programs (``bench/heldout.py``), of
min(predicted, measured) / max(predicted, measured): each prediction priced
by ``HloLatencyEstimator`` from the rows this run measured, each
measurement blocked calls on the chip (``time_heldout`` in
``bench/drivers/characterize.py``)."""
import math


def read(run):
    pred = run.data.get("heldout_predicted_s")
    if not pred:
        return None
    meas = run.data["heldout_measured_s"]
    logs = [math.log(min(pred[k], meas[k]) / max(pred[k], meas[k]))
            for k in pred]
    return math.exp(sum(logs) / len(logs))
