import pathlib
import sys

# the program under test lives in src/ (bench/run.py puts it on the path
# the same way)
SRC = str(pathlib.Path(__file__).resolve().parents[2] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
