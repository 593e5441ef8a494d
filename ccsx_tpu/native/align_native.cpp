// Scalar affine-gap pairwise aligner (C++), the native reference
// implementation for differential testing (SURVEY.md §7.2 step 2).
//
// Semantics are pinned to the NumPy oracle (ccsx_tpu/ops/oracle.py), which
// itself replicates what ccsx consumes from bsalign's
// kmer_striped_seqedit_pairwise (main.c:264, result fields main.c:272-280):
// Gotoh affine-gap DP, modes global / qfree (query ends free) / local,
// traceback preferring diagonal, then vertical (E), then horizontal (F) on
// ties; first-occurrence argmax for free end cells.  The differential test
// (tests/test_native_align.py) requires exact equality of score, spans,
// counts and cigar against the oracle.

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

constexpr int32_t kNeg = -(1 << 29);

enum Mode { kGlobal = 0, kQfree = 1, kLocal = 2 };

// One byte per cell records what the oracle's traceback asks of the
// score matrices, so only two score rows are kept: (Q+1)(T+1) bytes,
// not three int32 matrices — a 20 kb x 20 kb pair fits in 400 MB.
enum Bit : uint8_t {
  kDiag = 1,   // H(i,j) == H(i-1,j-1) + sub
  kEqE = 2,    // H(i,j) == E(i,j)
  kEqF = 4,    // H(i,j) == F(i,j)
  kExtE = 8,   // E(i,j) == E(i-1,j) + gap_ext
  kExtF = 16,  // F(i,j) == F(i,j-1) + gap_ext
  kZero = 32,  // H(i,j) == 0
};

}  // namespace

extern "C" {

// out[10] = score qb qe tb te aln mat mis ins del.
// cigar (optional, may be null): expanded per-column ops 'M'/'I'/'D';
// *cigar_n receives the op count, or -1 when cigar_cap was too small
// (stats in `out` remain valid).
// Returns 0 ok, -1 bad args / problem too large for the scalar path.
int ccsx_align_scalar(const uint8_t* q, int64_t qlen, const uint8_t* t,
                      int64_t tlen, int mode, int match, int mismatch,
                      int gap_open, int gap_ext, int64_t* out, uint8_t* cigar,
                      int64_t cigar_cap, int64_t* cigar_n) {
  if (qlen < 0 || tlen < 0 || !out) return -1;
  if ((qlen + 1) * (tlen + 1) > (int64_t)1 << 29) return -1;  // 512 MB cap
  if (mode != kGlobal && mode != kQfree && mode != kLocal) return -1;
  const int oe = gap_open + gap_ext;
  const int64_t W = tlen + 1;
  std::vector<uint8_t> dir((size_t)((qlen + 1) * W), 0);
  std::vector<int32_t> Hp(W, kNeg), Ep(W, kNeg), Hc(W), Ec(W);

  auto subst = [&](int64_t i, int64_t j) -> int32_t {
    // N (code >= 4) never matches anything, including itself
    return (q[i] == t[j] && q[i] < 4 && t[j] < 4) ? match : mismatch;
  };
  // first-occurrence argmax in row-major order, matching numpy
  int64_t ei = qlen, ej = tlen;
  int32_t best = kNeg - 1;
  auto track = [&](int64_t i, int64_t j, int32_t h) {
    if (mode == kQfree ? (j == tlen && h > best) : (mode == kLocal &&
                                                    h > best)) {
      best = h; ei = i; ej = j;
    }
  };

  // row 0: H, F initialised; E = kNeg
  {
    int32_t fprev = kNeg;
    for (int64_t j = 0; j <= tlen; j++) {
      int32_t h = kNeg, f = kNeg;
      if (j == 0) h = 0;
      else if (mode == kLocal) h = 0;
      else h = f = gap_open + (int32_t)j * gap_ext;
      uint8_t b = 0;
      if (h == f) b |= kEqF;
      if (h == kNeg) b |= kEqE;
      if (j > 0 && f == fprev + gap_ext) b |= kExtF;
      if (h == 0) b |= kZero;
      dir[j] = b;
      Hp[j] = h;
      fprev = f;
      track(0, j, h);
    }
  }
  for (int64_t i = 1; i <= qlen; i++) {
    uint8_t* d = &dir[(size_t)(i * W)];
    // E for the whole row (column 0 included), as the oracle does
    for (int64_t j = 0; j <= tlen; j++) {
      int32_t e1 = Hp[j] + oe, e2 = Ep[j] + gap_ext;
      Ec[j] = e1 > e2 ? e1 : e2;
    }
    int32_t h0 = mode == kGlobal ? gap_open + (int32_t)i * gap_ext : 0;
    Hc[0] = h0;
    {
      uint8_t b = 0;
      if (h0 == Ec[0]) b |= kEqE;
      if (Ec[0] == Ep[0] + gap_ext) b |= kExtE;
      if (h0 == kNeg) b |= kEqF;
      if (h0 == 0) b |= kZero;
      d[0] = b;
      track(i, 0, h0);
    }
    int32_t fprev = kNeg;
    for (int64_t j = 1; j <= tlen; j++) {
      int32_t f1 = Hc[j - 1] + oe, f2 = fprev + gap_ext;
      int32_t f = f1 > f2 ? f1 : f2;
      int32_t diag = Hp[j - 1] + subst(i - 1, j - 1);
      int32_t h = diag;
      if (Ec[j] > h) h = Ec[j];
      if (f > h) h = f;
      if (mode == kLocal && h < 0) h = 0;
      if (h < kNeg) h = kNeg;
      uint8_t b = 0;
      if (h == diag) b |= kDiag;
      if (h == Ec[j]) b |= kEqE;
      if (h == f) b |= kEqF;
      if (Ec[j] == Ep[j] + gap_ext) b |= kExtE;
      if (f == fprev + gap_ext) b |= kExtF;
      if (h == 0) b |= kZero;
      d[j] = b;
      Hc[j] = h;
      fprev = f;
      track(i, j, h);
    }
    std::swap(Hp, Hc);
    std::swap(Ep, Ec);
  }
  int32_t score = mode == kGlobal ? Hp[tlen] : best;

  // --- traceback (diag > E > F on ties, like the oracle) ---
  auto at = [&](int64_t i, int64_t j) -> uint8_t {
    return dir[(size_t)(i * W + j)];
  };
  int64_t i = ei, j = ej;
  int64_t mat = 0, mis = 0, ins = 0, del = 0;
  std::vector<uint8_t> ops;  // reversed
  char state = 'H';
  for (;;) {
    if (state == 'H') {
      uint8_t b = at(i, j);
      if (mode == kLocal && (b & kZero)) break;
      if (mode == kQfree && j == 0) break;
      if (mode == kGlobal && i == 0 && j == 0) break;
      if (i > 0 && j > 0 && (b & kDiag)) {
        ops.push_back('M');
        if (q[i - 1] == t[j - 1] && q[i - 1] < 4) mat++; else mis++;
        i--; j--;
      } else if (i > 0 && (b & kEqE)) {
        state = 'E';
      } else if (j > 0 && (b & kEqF)) {
        state = 'F';
      } else {
        state = i > 0 ? 'E' : 'F';
      }
    } else if (state == 'E') {
      ops.push_back('I');
      ins++;
      if ((at(i, j) & kExtE) && i > 1) { i--; }
      else { i--; state = 'H'; }
    } else {
      ops.push_back('D');
      del++;
      if ((at(i, j) & kExtF) && j > 1) { j--; }
      else { j--; state = 'H'; }
    }
  }

  out[0] = score;
  out[1] = i;   // qb
  out[2] = ei;  // qe
  out[3] = j;   // tb
  out[4] = ej;  // te
  out[5] = mat + mis + ins + del;
  out[6] = mat;
  out[7] = mis;
  out[8] = ins;
  out[9] = del;
  if (cigar_n) {
    if (cigar && (int64_t)ops.size() <= cigar_cap) {
      for (size_t k = 0; k < ops.size(); k++)
        cigar[k] = ops[ops.size() - 1 - k];
      *cigar_n = (int64_t)ops.size();
    } else {
      *cigar_n = -1;
    }
  }
  return 0;
}

}  // extern "C"
