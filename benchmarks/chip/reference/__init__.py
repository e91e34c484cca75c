"""Plain references the checks compare the program with."""
