"""The yardstick for lightgbm-tpu: see benchmark/README.md."""
