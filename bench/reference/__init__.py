"""Plain references the benchmark compares the served answers with.  They
import nothing of the program and take nothing it has made."""
