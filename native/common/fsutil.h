// Filesystem helpers shared by daemons.
#pragma once

#include <cstdint>
#include <string>

namespace fdfs {

bool MakeDirs(const std::string& path);          // mkdir -p
bool EnsureParentDirs(const std::string& path);  // mkdir -p dirname(path)
bool ReadWholeFile(const std::string& path, std::string* out);
// Bytes [off, off + len) of a file, all of them or false (EINTR retried).
bool PreadFull(int fd, char* dst, int64_t len, int64_t off);

}  // namespace fdfs
