"""The chip benchmark: MLPerf-Tiny networks through MATCH's compile -> AOT ->
batch -> serve path on a TPU.  ``python3 -m benchmarks.chip --help``."""
