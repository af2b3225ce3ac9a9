"""The benchmark's yardstick: traffic generation, costs and peaks, trace
reduction, statistics and the correctness comparison."""
