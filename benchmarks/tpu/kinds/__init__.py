"""Job kinds: one module per kind of work a job file can name."""
