"""Simulated rounds per second over whole study runs: scenarios x seeds
x rounds of every completed run, over the time from the first dispatch
to the last result on the host."""


def read(run):
    if "scenario_rounds" not in run.facts:
        return None
    return run.facts["scenario_rounds"] / run.facts["elapsed_s"]
