from repro.roofline.analysis import (Roofline, analyze, parse_collectives,
                                     parse_collectives_by_computation,
                                     split_computations,
                                     innermost_loop_collectives,
                                     model_flops_for, PEAKS, ChipPeaks,
                                     chip_peaks)

__all__ = ["Roofline", "analyze", "parse_collectives",
           "parse_collectives_by_computation", "split_computations",
           "innermost_loop_collectives", "model_flops_for",
           "PEAKS", "ChipPeaks", "chip_peaks"]
