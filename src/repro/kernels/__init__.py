# Kernel package: Pallas TPU kernels for the paper's 27-permutation
# mixed-precision library, plus the dispatch registry (dispatch.py) and the
# tile autotuner (tuning.py). ops.py is the one place that decides whether a
# kernel runs compiled (on a TPU) or in the Pallas interpreter.
