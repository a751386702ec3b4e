"""The on-chip benchmark's yardstick: data and traffic generation, the
plain reference, trace reduction, peaks and work counts."""
