"""The plain reference the output check compares against: a float32
forward of the configuration, weights made again from the seed, and its
fp8 control. Imports nothing of the program."""
