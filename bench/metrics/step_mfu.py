"""The whole forward's share of the chip's bf16 peak: the uncoded model's
FLOPs per image (counted from the reference's shapes; redundant coded work
does not count) times the images per second of the traced window."""


def read(rec):
    if rec.peaks is None or rec.window_s <= 0 or not rec.images:
        return None
    achieved = rec.model_flops_per_image * rec.images / rec.window_s
    return achieved / rec.peaks["bf16_flops_per_s"] * 100.0
