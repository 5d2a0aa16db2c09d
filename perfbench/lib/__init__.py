"""The benchmark's own code: traffic, load generation, arithmetic, the
plain reference, the trace reduction. Nothing here is imported by the
program under test."""
