"""BinSketch engine benchmark: harness, traffic generator, reference and readers."""
