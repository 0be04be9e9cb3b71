"""Model FLOP/s utilisation: the FLOPs the forward and backward passes need at
the width each trained client holds (`benchmark/flops/<model>.py`, from
shapes; masked-out channels and recomputation do not count), per device-busy
second, over chips x the chip's bf16 peak."""


def compute(reduction, phases, cell):
    if not reduction:
        return None
    busy = reduction["busy_s"] * cell["chips"]  # device-seconds
    return 100.0 * cell["model_flops_per_round"] * reduction["rounds"] / busy \
        / cell["peak_flops_per_s"]
