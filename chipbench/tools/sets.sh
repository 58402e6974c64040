# Run one cell once per seed, each run a new process, and print one line
# per run: its exit code, wall time, `correct`, metrics and checked numbers.
#
#   OUT=<dir> bash chipbench/tools/sets.sh <workload> <tag> <seconds> <seeds...>
#
# Each run's stdout and stderr go to <dir>/<workload>_<tag>_<seed>.{out,err}
# (default dir: chipbench_runs, which .gitignore lists).
w=$1; tag=$2; secs=$3; shift 3
out=${OUT:-chipbench_runs}
mkdir -p "$out"
for s in "$@"; do
  T0=$(date +%s)
  python3 chipbench/run.py --workload $w --seed $s --seconds $secs --trace 0 > "$out/${w}_${tag}_$s.out" 2> "$out/${w}_${tag}_$s.err"
  rc=$?
  echo "$w $tag seed $s rc=$rc $(( $(date +%s)-T0 ))s $(tail -1 "$out/${w}_${tag}_$s.out" | python3 -c 'import json,sys; d=json.loads(sys.stdin.read()); print(d["correct"], {k: round(v["value"],4) for k,v in d["metrics"].items()}, {k: "%.3g"%v["value"] for k,v in d["checked"].items()})' 2>/dev/null)"
done
