"""Plain references: the same semantics as the program, written
independently of it (they import nothing from `repro`)."""
