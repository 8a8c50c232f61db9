"""Whole round's share of the chips' bf16 peak: the operations each job's
rounds need (``bench/work/<config work>.py``, from shapes and that job's
partition) over the traced window's wall time, over chips x peak."""


def read(view):
    work = view.work(view.cell.config["work"])
    per_job = view.rounds / len(view.results)
    flops = sum(work.flops_per_round(view.graph, r["partition"].owner, view.cell.config,
                                     view.cell.job) * per_job for r in view.results)
    return 100.0 * flops / view.wall_s / (view.chips * view.peaks["bf16_flops_per_s"])
