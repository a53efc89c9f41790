"""Model FLOP/s utilization: FLOPs of the conv and dense layers counted from
the configuration's shapes (``benchmark/flops.py``: 2 x multiply-adds x 3 for
forward and backward) x images per second / (chips x the peak of
``benchmark/peaks.json``). Over 100 % raises."""
import flops


def read(run):
    cell = run["cell"]
    if cell.peaks is None:
        return None
    per_image = flops.resnet_v1_train_flops_per_image(cell.config["model"])
    achieved = per_image * run["end_to_end"]["train_img_per_s"]
    return flops.share_of_peak(
        achieved, cell.chips * cell.peaks["bf16_flops_per_s"], "train_mfu_pct")
