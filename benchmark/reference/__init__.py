"""The plain reference and the seeded weights (imports nothing of the program)."""
