"""The benchmark's yardstick, shared by every architecture: the harness,
traffic generator, weights from the seed, the comparison that decides
``correct``, the roofline and trace reduction.  What belongs to one
architecture (layout, work counts, plain reference) is in its module
under ``bench/arch/``."""
