"""The loops that drive the program under test through set-up, warm-up,
the measured window and the check.  A configuration file names its
driver."""
