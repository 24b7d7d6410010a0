#!/bin/bash
# The committed files are enough: run a cell from a copy of what git would
# commit, at another path, with nothing of the working tree beside it.
#   (here)   git add -A && rm -rf .bench_proof && mkdir .bench_proof && \
#            git archive $(git write-tree) | tar -x -C .bench_proof
#   chiprun --chips 1 -- bash benchmark/tools/proof.sh <cell> <seconds> <seed>
set -u
cd .bench_proof || exit 2
python3 -m benchmark.run --workload "$1" --seed "$3" --seconds "$2" --trace 0 \
    > ../chiprun_out/proof.$1.$3.log 2>&1
rc=$?
echo "== proof $1 seed $3 rc=$rc in $(pwd)"
grep -E '^\[(setup|segments|batches|correct)' ../chiprun_out/proof.$1.$3.log | cut -c1-700
tail -n 1 ../chiprun_out/proof.$1.$3.log | cut -c1-1500
exit $rc
