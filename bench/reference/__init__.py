"""Plain references the check compares the program with."""
