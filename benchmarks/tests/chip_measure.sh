#!/bin/bash
# By hand, on the chip: `chiprun --timeout 3400 -- bash benchmarks/tests/chip_measure.sh <phase> [cells...]`
#   first <cells>   : one cold --trace 1 run of 12 s, then 3 --trace 0 runs of 20 s
#   calib <cells>   : benchmarks/tests/calibrate.py, 12 seeds and 3 control seeds a cell
#   entry <cells>   : benchmarks/tests/entry_check.py, run_training on the cell's config and data, 3 epochs
#   sets  <cells>   : two sets of 6 --trace 0 runs at run_seconds (same seeds in both), then 3 --trace 1 runs
# Everything lands in chiprun_out/; a phase goes on when one run fails.
phase=$1; shift
mkdir -p chiprun_out
secs=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
run() {  # name workload seed seconds trace
  local name=$1; shift
  python3 benchmarks/run.py --workload $1 --seed $2 --seconds $3 --trace $4 \
    > chiprun_out/$name.out 2> chiprun_out/$name.err
  echo "== $name rc=$? $(tail -n 1 chiprun_out/$name.out | cut -c1-1800)"
  grep -E "^compared|^correct|Error|error:" chiprun_out/$name.err | tail -n 8 | cut -c1-300
}
for cell in "$@"; do
  case $phase in
    first)
      run ${cell}_t1 $cell 3000000001 12 1
      for s in 11 12 13; do run ${cell}_s$s $cell $s 20 0; done ;;
    probe)
      python3 benchmarks/tests/memory_probe.py > chiprun_out/memory_probe.out 2>&1; tail -n 6 chiprun_out/memory_probe.out ;;
    calib)
      python3 benchmarks/tests/calibrate.py $cell 12 3 chiprun_out/${cell}_calib.jsonl $CALIB_EXTRA \
        > chiprun_out/${cell}_calib.out 2> chiprun_out/${cell}_calib.err
      echo "== calib $cell rc=$?"; cut -c1-330 chiprun_out/${cell}_calib.out; tail -n 5 chiprun_out/${cell}_calib.err | cut -c1-300 ;;
    entry)
      python3 benchmarks/tests/entry_check.py $cell 3 chiprun_out/${cell}_entry.json \
        > chiprun_out/${cell}_entry.out 2> chiprun_out/${cell}_entry.err
      echo "== entry $cell rc=$?"; tail -n 1 chiprun_out/${cell}_entry.out | cut -c1-3000; tail -n 5 chiprun_out/${cell}_entry.err | cut -c1-300 ;;
    sets)
      for set in a b; do for s in 2147483651 2147483652 2147483653 2147483654 2147483655 2147483656; do
        run ${cell}_set${set}_$s $cell $s $secs 0; done; done
      for s in 2147483661 2147483662 2147483663; do run ${cell}_tr_$s $cell $s $secs 1; done ;;
  esac
done
