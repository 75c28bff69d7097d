"""The chip benchmark's own code: cell lookup, traffic generation, the
plain reference, trace reduction and the work formulas.  It imports the
program under test (``repro``) only to drive it."""
