"""The benchmark's yardstick: peaks, work counts, trace reduction, loaders."""
