"""The benchmark's yardstick: the harness, traffic generator, weights,
plain reference, work counters and trace reduction."""
