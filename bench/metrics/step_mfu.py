"""The simulator step's share of the chips' bf16 peak: FLOPs the
algorithm requires (forward and backward of the participating clients'
batches, the held-out loss after every round, the accuracy evaluations)
over the traced window, the chips and the peak. Rows of clients that do
not participate are computed by the program but not counted."""

from metrics import _counts


def read(run):
    f, cfg = run.facts, run.cfg
    if "client_rounds" not in f or run.record is None:
        return None
    m, d = cfg["model"], cfg["data"]
    flops = (f["client_rounds"] * cfg["batch_size"]
             * _counts.cnn_train_flops(m)
             + (f["loss_evals"] * d["n_loss"] + f["acc_evals"] * d["n_eval"])
             * _counts.cnn_forward_flops(m))
    return 100 * flops / (run.window_s * run.chips
                          * run.peaks["bf16_flops_per_s"])
