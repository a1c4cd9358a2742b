"""The chip benchmark of dnn_tpu: see README.md here and PERF.md at the root."""
