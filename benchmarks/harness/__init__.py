"""The yardstick: everything here measures the program, none of it is the program."""
