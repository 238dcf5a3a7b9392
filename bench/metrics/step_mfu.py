"""The simulator step's share of the chips' bf16 peak: FLOPs the
algorithm requires (forward and backward of the participating clients'
batches, the held-out loss after every round, the accuracy evaluations)
over the traced window, the chips and the peak. The FLOPs of one
example (an image, a sequence) come from the ``counts`` module the
configuration names. Rows of clients that do not participate are
computed by the program but not counted."""


def read(run):
    f, cfg = run.facts, run.cfg
    if "client_rounds" not in f or run.record is None:
        return None
    d = cfg["data"]
    flops = (f["client_rounds"] * cfg["batch_size"]
             * run.counts.train_flops(cfg)
             + (f["loss_evals"] * d["n_loss"] + f["acc_evals"] * d["n_eval"])
             * run.counts.forward_flops(cfg))
    return 100 * flops / (run.window_s * run.chips
                          * run.peaks["bf16_flops_per_s"])
