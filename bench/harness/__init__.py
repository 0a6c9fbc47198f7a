"""The benchmark's traffic loops, generator, metric arithmetic and trace reduction."""
