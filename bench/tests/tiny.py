"""Tiny sizes of the cells for the CPU tests: widths and fleets cut so a
whole run takes seconds. The cuts are made here only, never in a run on
the chip."""


def shrink(cell):
    s = cell.config["sizes"]
    if cell.config["model"] == "logreg":
        s["n"], s["dim"] = 2048, 8
    t = cell.traffic
    t["num_chains"] = min(int(t["num_chains"]), 16)
    t["num_warmup"] = min(int(t["num_warmup"]), 100)
    t["num_samples"] = min(int(t["num_samples"]), 100)
