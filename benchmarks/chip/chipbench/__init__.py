"""The chip benchmark's harness: registry, window, trace reduction, check."""
