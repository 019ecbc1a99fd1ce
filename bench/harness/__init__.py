"""The benchmark's yardstick: lake, traffic, reference, comparison,
trace reduction, work counts and peaks."""
