"""The chip benchmark's own code: traffic, probe, trace reduction,
correctness check and the run itself.  Nothing here is imported by the
program; the program is imported only by ``harness``."""
