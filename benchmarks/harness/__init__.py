"""The benchmark's own code: everything the yardstick is made of lives
under `benchmarks/`; from the program it takes only the system under
test (`paddle_tpu`) and what that reports about itself."""
