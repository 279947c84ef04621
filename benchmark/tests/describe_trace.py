import sys, json, os
sys.path.insert(0, "benchmark")
from harness import trace as T
p = T.find_xplane("benchmark/.trace")
rows = T.describe(p, 15)
os.makedirs("chiprun_out", exist_ok=True)
json.dump(rows, open("chiprun_out/trace_describe.json", "w"), indent=1)
for r in rows:
    print(r["plane"], "|", r["line"], "|", r["events"], "|", [n for n, _ in r["top"][:4]])
