"""The yardstick: everything the benchmark measures with lives under
benchmark/, so a PR that changes the program cannot change it."""
